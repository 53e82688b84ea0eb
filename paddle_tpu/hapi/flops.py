"""paddle.flops — model FLOPs via XLA's own cost analysis (reference
hapi/dynamic_flops.py counts per-layer by formula; XLA counts the actual
compiled HLO, which also covers custom/fused ops for free)."""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..autograd.engine import no_grad
from ..core.tensor import Tensor
from ..jit.api import _traced_rng


def flops(net, input_size: Optional[Sequence[int]] = None, inputs=None,
          custom_ops=None, print_detail: bool = False) -> int:
    """Total forward FLOPs for `net`, on zeros of `input_size` or on the
    given `inputs` (list of Tensors/arrays — required for multi-input or
    integer-dtype models)."""
    import numpy as np
    was_training = net.training
    net.eval()
    try:
        def fn(*xs):
            with no_grad(), _traced_rng(jax.random.key(0)):
                return net(*[Tensor(x) for x in xs])._data

        if inputs is not None:
            seq = inputs if isinstance(inputs, (list, tuple)) else [inputs]
            arrays = [a._data if isinstance(a, Tensor)
                      else jnp.asarray(np.asarray(a)) for a in seq]
        elif input_size is not None:
            arrays = [jnp.zeros(tuple(input_size), jnp.float32)]
        else:
            raise ValueError("flops: provide input_size or inputs")
        compiled = jax.jit(fn).lower(*arrays).compile()
        cost = compiled.cost_analysis()
        total = int(cost.get("flops", 0))
        if print_detail:
            print(f"Total FLOPs: {total:,} "
                  f"(bytes accessed: {int(cost.get('bytes accessed', 0)):,})")
        return total
    finally:
        if was_training:
            net.train()
