"""Pure-jnp oracles for every Pallas kernel (the correctness contracts)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0**30


def ref_attention(
    q: jax.Array,            # [B, Sq, H, D]
    k: jax.Array,            # [B, Sk, K, D]
    v: jax.Array,            # [B, Sk, K, D]
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
) -> jax.Array:
    b, sq, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    scale = d**-0.5 if scale is None else scale
    qg = q.reshape(b, sq, kh, g, d)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    sk = k.shape[1]
    qpos = jnp.arange(sq)[:, None] + (sk - sq)
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    return o.reshape(b, sq, h, d)


def ref_rglru(
    a: jax.Array,    # [B, S, W] per-step decay in (0,1], f32
    x: jax.Array,    # [B, S, W] gated inputs
    h0: jax.Array,   # [B, W] initial state
) -> tuple[jax.Array, jax.Array]:
    """h_t = a_t * h_{t-1} + x_t. Returns (ys [B,S,W], h_final [B,W])."""

    def step(h, inp):
        a_t, x_t = inp
        h = a_t * h + x_t
        return h, h

    af = a.astype(jnp.float32).swapaxes(0, 1)
    xf = x.astype(jnp.float32).swapaxes(0, 1)
    hf, ys = jax.lax.scan(step, h0.astype(jnp.float32), (af, xf))
    return ys.swapaxes(0, 1), hf


def ref_wkv6(
    r: jax.Array,    # [B, S, H, K]
    k: jax.Array,    # [B, S, H, K]
    v: jax.Array,    # [B, S, H, V]
    w: jax.Array,    # [B, S, H, K] per-step decay in (0,1)
    u: jax.Array,    # [H, K] bonus
    s0: jax.Array,   # [B, H, K, V] initial state
) -> tuple[jax.Array, jax.Array]:
    """y_t = rᵗ(S + u⊙k vᵀ); S ← w⊙S + k vᵀ. Returns (y, S_final)."""

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp
        kv = jnp.einsum("bhk,bhv->bhkv", k_t, v_t)
        y = jnp.einsum("bhk,bhkv->bhv", r_t, S + u[None, :, :, None] * kv)
        S = w_t[..., None] * S + kv
        return S, y

    seq = tuple(
        z.swapaxes(0, 1).astype(jnp.float32) for z in (r, k, v, w)
    )
    S, ys = jax.lax.scan(step, s0.astype(jnp.float32), seq)
    return ys.swapaxes(0, 1), S
