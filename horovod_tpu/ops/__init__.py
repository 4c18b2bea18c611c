"""horovod_tpu.ops subpackage: the floor. Collectives and their backends
(``collectives``, ``dispatch``, ``adasum``), the dense attention reference
(``attention``), and the Pallas kernel families over ``pallas_util``: flash
attention (``flash_attention``), the Mamba-2 chunked scan (``ssd``), Mamba-1's
selective scan (``s6``), the chunked gated delta rule (``gated_delta``), Kimi
delta attention, that rule with a decay a key channel (``kda``), the
causal depthwise convolution in front of the scans (``conv``), a CCA mixer's
mix (``cca``). Nothing here imports ``parallel/``, ``compression/`` or
``models/``."""
