"""Names of the fused train step's device regions.

The step's work is wrapped in ``jax.named_scope`` regions of these names,
so every HLO instruction carries one of them in its ``op_name`` metadata
(a path component such as ``jit(step)/.../exchange/...``) and a profiler
trace can be read by program region instead of by op family:

- ``exchange`` — the §5.2 feature exchange: the emulated ``jnp.take``s,
  the ``ShardComm`` all-to-alls, and the ``[local | cached | fetched]``
  workspace concatenation with its one layout in the gather kernel's
  words (pad to whole lane tiles, reshape);
- ``gather``   — ``kernels.ops.gather_rows``; inside it, ``kernel`` wraps
  only the Pallas call, and the rest of ``gather`` is the work around it
  (the index pad, the slice of the output back to the feature width);
- ``layers``   — the model's loss, forward and (through autodiff)
  backward; inside it, GNN-FiLM's layers name their two parts:
  ``film.edges`` (the per-edge transform, modulation, activation and
  mean over the children) and ``film.nodes`` (both hypernetworks, the
  self message and the LayerNorm);
- ``update``   — the gradient reduction and the optimizer update.

Scopes are metadata only: they change no numerics and cost nothing at
run time. View them in the ``jax.profiler`` trace (TensorBoard/xprof).
"""
EXCHANGE = "exchange"
GATHER = "gather"
KERNEL = "kernel"
LAYERS = "layers"
FILM_EDGES = "film.edges"
FILM_NODES = "film.nodes"
UPDATE = "update"
