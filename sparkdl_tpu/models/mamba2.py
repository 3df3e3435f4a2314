"""The Mamba-2 mixer of a hybrid decoder's state-space layers.

    [z | xBC | dt] = in_proj(u)
    xBC = silu(causal depthwise conv1d(xBC) + b);  x, B, C = split(xBC)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T;  y_t = h_t C_t + D x_t
    y = RMSNorm by group(y * silu(z)) * w;  out_proj(y)

The recurrence runs in its chunked form (:func:`sparkdl_tpu.ops.ssd.
ssd_chunked`). ``in_proj`` and ``out_proj`` are ``llama._dense``'s, so
LoRA reaches them by name. Training only: there is no recurrent state
beside a serving cache yet (PERF.md, section 7).
"""

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from sparkdl_tpu.models.llama import _dense
from sparkdl_tpu.ops.ssd import ssd_chunked


def _dt_bias_init(lo, hi, floor):
    """``dt_bias`` whose softplus is log-uniform in [lo, hi], as the
    published initialisation has it."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(
        key, shape, minval=1.0, maxval=16.0)).astype(dtype)


def causal_conv1d(x, kernel, bias):
    """Depthwise over the sequence: ``out[t] = sum_j kernel[j] *
    x[t - (taps - 1) + j] + bias``, zeros before the start.
    x (batch, seq, channels), kernel (taps, channels); float32."""
    taps, s = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    kernel = kernel.astype(jnp.float32)
    return sum(kernel[j] * padded[:, j:j + s] for j in range(taps)) \
        + bias.astype(jnp.float32)


class Mamba2Mixer(nn.Module):
    """``cfg`` is a :class:`~sparkdl_tpu.models.hybrid.HybridConfig`."""

    cfg: Any

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        b, s, _ = u.shape
        heads, p = cfg.ssm_heads, cfg.ssm_head_dim
        groups, n = cfg.ssm_groups, cfg.ssm_state
        inner, bc = heads * p, groups * n
        z, xbc, dt = jnp.split(
            _dense(cfg.attn, 2 * inner + 2 * bc + heads, "in_proj")(u),
            [inner, 2 * inner + 2 * bc], axis=-1)
        with jax.named_scope("sparkdl.ssm.conv"):
            xbc = nn.silu(causal_conv1d(
                xbc,
                self.param("conv_kernel", nn.initializers.lecun_normal(),
                           (cfg.conv_kernel, inner + 2 * bc)),
                self.param("conv_bias", nn.initializers.zeros,
                           (inner + 2 * bc,)))).astype(cfg.dtype)
        x, B, C = jnp.split(xbc, [inner, inner + bc], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + self.param(
            "dt_bias", _dt_bias_init(cfg.time_step_min, cfg.time_step_max,
                                     cfg.time_step_floor),
            (heads,)).astype(jnp.float32))
        A = -jnp.exp(self.param("A_log", _a_log_init, (heads,))
                     .astype(jnp.float32))
        D = self.param("D", nn.initializers.ones, (heads,))
        with jax.named_scope("sparkdl.ssm.scan"):
            y = ssd_chunked(
                x.reshape(b, s, heads, p), dt, A,
                B.reshape(b, s, groups, n), C.reshape(b, s, groups, n), D,
                chunk=cfg.chunk_size)
        # the gated norm: groups of inner / groups channels
        y = y.reshape(b, s, inner).astype(jnp.float32) * nn.silu(
            z.astype(jnp.float32))
        y = y.reshape(b, s, groups, inner // groups)
        y = y * jax.lax.rsqrt(
            jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_eps)
        y = y.reshape(b, s, inner) * self.param(
            "norm_scale", nn.initializers.ones, (inner,))
        return _dense(cfg.attn, u.shape[-1], "out_proj")(y.astype(cfg.dtype))
