"""The gated short convolution of a hybrid decoder layer (LFM2).

After HF ``Lfm2ShortConv`` (``conv_L_cache`` taps; no bias anywhere, as the one
model that has the layer publishes it: ``conv_bias`` false), for one layer's
normed input ``u``:

    [B | C | X] = W_in u                       three slices as wide as the model, in that order
    z = B * X
    c_t = sum_i w[:, i] z_{t - (L - 1) + i}    causal, depthwise, no activation; zeros before the prompt
    out = W_out (C * c)

What a request carries from token to token is ``z``'s last ``L - 1`` values a
channel and nothing else: there is no state matrix. The tails live in the
``conv`` half of the engine's recurrent store, laid out as the other mixers'
(a row's taps side by side); the store's ``ssm`` half is empty for this kind
(``init_recurrent_store``). Padding must not enter a tail: the new tail is
taken at the row's last valid position.

The two projections compute in ``dtype`` like every matmul of the decoder and
``z`` is their product in that type (it is what the tails store); the
convolution sums in float32 and its taps are stored in float32
(``VLM.param_dtype`` has the rule). The whole mixer stands under the scope
``mixer.short_conv`` in a compiled program.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from cosmos_curate_tpu.models.layers import dense
from cosmos_curate_tpu.models.vlm.mamba2 import conv_with_tail

SCOPE = "mixer.short_conv"


class ShortConvMixer(nn.Module):
    cfg: Any  # model.ShortConvConfig
    dim: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h, tail, valid):
        """h: [B, T, D]; tail: ``[B, (l_cache - 1) * D]``, the rows' last
        convolution inputs; valid: [B] leading positions of the chunk that are
        tokens. Returns (out [B, T, D], the new tail)."""
        m = self.cfg
        d = h.shape[-1]
        f32 = jnp.float32
        proj = lambda n, name: dense(  # noqa: E731
            n, None, name=name, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype
        )
        with jax.named_scope(SCOPE):
            gate_in, gate_out, x = jnp.split(proj(3 * d, "in_proj")(h), 3, axis=-1)
            w = self.param("conv_kernel", nn.initializers.normal(0.2), (m.l_cache, d), f32)
            c, new_tail = conv_with_tail(gate_in * x, tail, w, valid)
            out = proj(d, "out_proj")((gate_out.astype(f32) * c).astype(self.dtype))
        return out, new_tail
