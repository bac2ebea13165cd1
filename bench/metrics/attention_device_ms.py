"""Device milliseconds per step of full-sequence attention: the leaf
operations under the model's named scope ``model.attention``
(``models/blocks.py _attend_full``), forward, recomputed forward and
backward, whichever path ran (the fused kernels' ``flash_mha_*`` calls, or
the jnp scan); see bench/metrics/_scopes.py."""
from bench.metrics._scopes import device_ms


def read(ctx):
    return device_ms(ctx, "model.attention")
