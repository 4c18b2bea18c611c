"""A layer's mixer is one module here and one entry of ``MIXERS``, where a
block looks it up (``gpt._sublayers``). Each gives the same things under the
same names: ``init(keys, cfg, dense, norm)`` (``keys``: the layer's first
four) and ``specs(cfg)``, both read off one table so that a parameter's name
is written once; ``apply(cfg, spec, params, h, positions)``, the branch on
normed activations; how it is spelled in a block, ``KEY`` (the layer's
sub-dict of its parameters; None: the layer's root), ``NORM`` (its norm's
key) and ``scope(spec)``; and ``SAVED_NAMES``, the names it gives
``checkpoint_name``. No mixer imports another, nor ``models/gpt.py``.

**A value may cross layers beside the stream**, through one carry
(``gpt._hidden``): a mixer says what it can hand on (``PUBLISHES``, names)
and what it is handed (``READS``); a layer's ``LayerSpec.publishes`` and
``.reads`` say which of them it does, ``config.layer_plan`` that every read
value has a producer before it, ``gpt._sublayers`` that the names are the
mixer's. A mixer with ``READS`` takes the values after ``positions``, in
that order; one with ``PUBLISHES`` returns ``(the branch, {name: value})``
for the names its ``spec.publishes`` holds. ``s6`` publishes its scan's
output to ``gmu``; ``diff_attention`` its keys and values to ``diff_cross``
(one module's two mixers: they differ in which projections a layer has)."""

from . import attention, cca, diff_attention, gdn, gmu, kda, mla, s6, ssm

MIXERS = {"attention": attention, "cca": cca, "mla": mla, "ssm": ssm,
          "gdn": gdn, "kda": kda, "s6": s6, "gmu": gmu,
          "diff_attention": diff_attention.SELF,
          "diff_cross": diff_attention.CROSS}
