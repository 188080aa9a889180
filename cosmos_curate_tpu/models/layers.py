"""Shared Flax building blocks with tensor-parallel sharding annotations.

TPU-first design: every weight matrix carries a ``nn.with_partitioning``
annotation over the ``model`` mesh axis following the standard Megatron
sharding recipe (public technique): attention QKV and MLP-up shard their
*output* features; attention-out and MLP-down shard their *input* features,
so each block needs exactly one ``psum`` (inserted automatically by XLA at
the sharded->replicated boundary). Replaces the reference's reliance on
vLLM-internal NCCL TP (SURVEY.md §2.7).

Compute dtype is bf16 by default (MXU-native). Parameters are stored in
float32 unless a model passes ``param_dtype`` down to ``dense`` (the caption
VLM does, so that its engine serves from the type it computes in).
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from cosmos_curate_tpu.parallel.axes import MODEL as MODEL_AXIS

Dtype = Any


def dense(
    features: int,
    shard: str | None,
    name: str | None = None,
    use_bias: bool = True,
    dtype=jnp.bfloat16,
    param_dtype=jnp.float32,
    precision=None,
):
    """Dense with kernel sharding: shard='out' partitions output features,
    'in' partitions input features, None replicates. ``dtype`` is the type
    the layer computes in, ``param_dtype`` the type its kernel and bias are
    stored in (flax casts them to ``dtype`` at every call where they differ);
    ``precision`` is the product's (None: the backend's default)."""
    if shard == "out":
        spec = (None, MODEL_AXIS)
        bias_spec = (MODEL_AXIS,)
    elif shard == "in":
        spec = (MODEL_AXIS, None)
        bias_spec = None  # bias on replicated output
    else:
        spec = (None, None)
        bias_spec = None
    kernel_init = nn.with_partitioning(nn.initializers.xavier_uniform(), spec)
    bias_init = nn.initializers.zeros
    if bias_spec is not None:
        bias_init = nn.with_partitioning(nn.initializers.zeros, bias_spec)
    return nn.Dense(
        features,
        use_bias=use_bias,
        dtype=dtype,
        param_dtype=param_dtype,
        precision=precision,
        kernel_init=kernel_init,
        bias_init=bias_init,
        name=name,
    )


# Above this sequence length self-attention is HBM-bound and the Pallas
# flash kernel wins (measured 1.9x at S=8192 on v5e); below it XLA's own
# fusion is as good or better, so we let the compiler handle it.
FLASH_MIN_SEQ = 2048


def _use_flash(s: int, mask) -> bool:
    import jax

    return (
        mask is None
        and s >= FLASH_MIN_SEQ
        and jax.devices()[0].platform == "tpu"
    )


class Attention(nn.Module):
    """Multi-head attention, heads sharded over the model axis."""

    num_heads: int
    head_dim: int
    dtype: Dtype = jnp.bfloat16
    causal: bool = False

    @nn.compact
    def __call__(self, x, mask=None):
        inner = self.num_heads * self.head_dim
        q = dense(inner, "out", name="q", dtype=self.dtype)(x)
        k = dense(inner, "out", name="k", dtype=self.dtype)(x)
        v = dense(inner, "out", name="v", dtype=self.dtype)(x)
        b, s, _ = x.shape
        q = q.reshape(b, s, self.num_heads, self.head_dim)
        k = k.reshape(b, s, self.num_heads, self.head_dim)
        v = v.reshape(b, s, self.num_heads, self.head_dim)
        if _use_flash(s, mask):
            from cosmos_curate_tpu.ops.flash_attention import flash_attention

            out = flash_attention(
                q.transpose(0, 2, 1, 3),
                k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3),
                causal=self.causal,
            ).transpose(0, 2, 1, 3)
        else:
            scale = self.head_dim**-0.5
            logits = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k).astype(jnp.float32)
            if self.causal:
                cm = jnp.tril(jnp.ones((s, s), bool))
                logits = jnp.where(cm[None, None], logits, -jnp.inf)
            if mask is not None:
                logits = jnp.where(mask, logits, -jnp.inf)
            probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
            probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(self.dtype), v)
        out = out.reshape(b, s, inner)
        return dense(x.shape[-1], "in", name="out", dtype=self.dtype)(out)


def quick_gelu(x):
    """OpenAI CLIP's activation: x * sigmoid(1.702 x)."""
    return x * jax.nn.sigmoid(1.702 * x)


_ACTIVATIONS: dict[str, Callable] = {"gelu": nn.gelu, "quick_gelu": quick_gelu}


class MlpBlock(nn.Module):
    hidden_mult: float = 4.0
    dtype: Dtype = jnp.bfloat16
    act: str = "gelu"

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        h = dense(int(d * self.hidden_mult), "out", name="up", dtype=self.dtype)(x)
        h = _ACTIVATIONS[self.act](h)
        return dense(d, "in", name="down", dtype=self.dtype)(h)


class TransformerBlock(nn.Module):
    num_heads: int
    head_dim: int
    hidden_mult: float = 4.0
    dtype: Dtype = jnp.bfloat16
    causal: bool = False
    act: str = "gelu"
    ln_eps: float = 1e-6

    @nn.compact
    def __call__(self, x, mask=None):
        y = nn.LayerNorm(dtype=jnp.float32, epsilon=self.ln_eps, name="ln1")(x)
        x = x + Attention(
            self.num_heads, self.head_dim, dtype=self.dtype, causal=self.causal, name="attn"
        )(y, mask)
        y = nn.LayerNorm(dtype=jnp.float32, epsilon=self.ln_eps, name="ln2")(x)
        x = x + MlpBlock(self.hidden_mult, dtype=self.dtype, act=self.act, name="mlp")(y)
        return x
