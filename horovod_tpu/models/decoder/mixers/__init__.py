"""A layer's mixer is one module here and one entry of ``MIXERS``, where a
block looks it up (``gpt._sublayers``). Each gives the same things under the
same names: ``init(keys, cfg, dense, norm)`` (``keys``: the layer's first
four) and ``specs(cfg)``, both read off one table so that a parameter's name
is written once; ``apply(cfg, spec, params, h, positions)``, the branch on
normed activations; how it is spelled in a block, ``KEY`` (the layer's
sub-dict of its parameters; None: the layer's root), ``NORM`` (its norm's
key) and ``scope(spec)``; and ``SAVED_NAMES``, the names it gives
``checkpoint_name``. No mixer imports another, nor ``models/gpt.py``."""

from . import attention, cca, gdn, mla, ssm

MIXERS = {"attention": attention, "cca": cca, "mla": mla, "ssm": ssm,
          "gdn": gdn}
