"""One module per architecture, found by a configuration's ``model_type``:
``bench/families/<model_type>.py`` (``model.family``).  A module gives:

* ``dims(spec)``: the sizes the benchmark computes with, from the
  published keys, as a flat dict of hashable values.  It holds at least
  ``L``, ``d``, ``H``, ``Hkv``, ``D`` and ``V`` (read by the FLOP and byte
  counts and the traffic) and ``matmul_params``, the weights every token
  multiplies (``counts.token_flops``).
* ``model_config(spec, m)``: the program's ``ModelConfig`` (``m`` is
  ``dims(spec)``).
* ``layer_leaves(m)``, ``top_leaves(m)``: name -> (shape, kind) of each
  layer's leaves and of the leaves outside the layers.  A dotted name is
  the leaf's path in the program's parameter pytree; kinds are drawn by
  ``model._draw``.
* ``embed(top, toks)``, ``layer_fn(dims_key, control)`` and
  ``head_fn(dims_key, control)``: the plain float32 forward pass that the
  reference runs (``reference.logits_at``), with the fp8 control when
  ``control`` is true.  ``dims_key`` is ``tuple(sorted(m.items()))``.
"""
