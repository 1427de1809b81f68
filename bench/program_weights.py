"""Hand the benchmark's int8 weights to the program in its own types."""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

from repro.quant import QuantizedLinear, QuantizedMLP


def quantized_mlp(e_in: int, layers: Sequence) -> QuantizedMLP:
    """``intquant.Layer`` list -> the program's ``QuantizedMLP`` (copies)."""
    return QuantizedMLP(e_in=e_in, layers=tuple(
        QuantizedLinear(w_q=jnp.asarray(l.w, jnp.int8),
                        bias_q=jnp.asarray(l.b, jnp.int32), shift=l.shift,
                        relu=l.relu, e_w=l.e_w, e_out=l.e_out)
        for l in layers))
