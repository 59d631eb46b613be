"""Selective state-space recurrence with a scalar decay a head (the
"SSD" layer: a state ``H`` [P, N] a head, ``H_t = exp(dt_t A) H_{t-1} +
dt_t x_t B_t^T``, ``y_t = H_t C_t + D x_t``), in the two forms a server
needs:

- ``ssm_chunked``: a whole sequence in chunks (prefill, the uncached
  forward). Inside a chunk the outputs are one masked product of decays
  (``(C B^T) * L`` against ``dt x``: matrix products, which is what the
  chip is fast at); between chunks the state is carried by a ``lax.scan``
  over the chunks. Linear in the sequence, and the same numbers as the
  recurrence in another order of sums.
- ``ssm_step``: the recurrence once, for a decode step's one token a lane.

Shapes: ``x`` [B, S, H, P] (``ssm_step``: no S), ``dt`` [B, S, H] (after
its softplus), ``A`` [H] (negative), ``B`` and ``C`` [B, S, G, N] with G
groups of H / G heads sharing one B and C, ``D`` [H]; a state is
[B, H, P, N] in float32. Decays, cumulative sums and every accumulation
are float32; the operands of the large products stay in ``x``'s type
(bf16 on the chip, float32 in the tests). No Pallas kernel: XLA's own
fusions (PERF.md has their share of the roofline).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def ssm_chunked(x, dt, A, B, C, D, chunk: int,
                state0: Optional[jax.Array] = None,
                valid: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over a whole sequence; returns ``(y [B,S,H,P] in
    x's type, the state after the last token [B,H,P,N] float32)``.

    ``state0``: the state before the first token (zeros where None).
    ``valid`` [B, S] bool marks the tokens that count: where it is false
    ``dt`` is taken as 0, so the decay is 1 and nothing is added, and the
    state after a padded bucket IS the state after its last valid token
    (the outputs at such positions are junk nobody reads). A sequence
    that ``chunk`` does not divide is padded the same way."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    r = h // g
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    pad = -s % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    nc = (s + pad) // chunk
    mm = x.dtype  # the large products' operands
    dt = dt.astype(F32).reshape(b, nc, chunk, g, r)
    xh = x.reshape(b, nc, chunk, g, r, p)
    xdt = (xh.astype(F32) * dt[..., None]).astype(mm)
    Bc = B.reshape(b, nc, chunk, g, n)
    Cc = C.reshape(b, nc, chunk, g, n)
    # log-decay from the chunk's start up to and including each token
    cums = jnp.cumsum(dt * A.astype(F32).reshape(g, r), axis=2)  # [b,c,l,g,r]

    # inside a chunk: y_l += sum_{s<=l} (C_l . B_s) exp(cums_l - cums_s) dt_s x_s
    cb = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc, preferred_element_type=F32)
    seg = (cums.transpose(0, 1, 3, 4, 2)[..., :, None]
           - cums.transpose(0, 1, 3, 4, 2)[..., None, :])  # [b,c,g,r,l,s]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp",
                   (cb[:, :, :, None] * decay).astype(mm), xdt,
                   preferred_element_type=F32)

    # what each chunk adds to the state at its own end
    to_end = jnp.exp(cums[:, :, -1:] - cums)  # [b,c,l,g,r]
    added = jnp.einsum(
        "bcsgn,bcsgrp->bcgrpn", Bc,
        (xdt.astype(F32) * to_end[..., None]).astype(mm),
        preferred_element_type=F32)
    whole = jnp.exp(cums[:, :, -1])  # [b,c,g,r]: a chunk's total decay

    def carry_state(state, per_chunk):
        keep, add = per_chunk
        return keep[..., None, None] * state + add, state

    first = (jnp.zeros((b, g, r, p, n), F32) if state0 is None
             else state0.astype(F32).reshape(b, g, r, p, n))
    last, before = lax.scan(
        carry_state, first,
        (whole.transpose(1, 0, 2, 3), added.transpose(1, 0, 2, 3, 4, 5)))
    before = before.transpose(1, 0, 2, 3, 4, 5)  # [b,c,g,r,p,n]: at chunk start

    # what the state a chunk starts from gives each of its tokens
    y = y + jnp.einsum("bclgn,bcgrpn->bclgrp", Cc, before.astype(mm),
                       preferred_element_type=F32) * jnp.exp(cums)[..., None]
    y = y + xh.astype(F32) * D.astype(F32).reshape(g, r)[:, :, None]
    y = y.reshape(b, nc * chunk, h, p)[:, :s]
    return y.astype(x.dtype), last.reshape(b, h, p, n)


def ssm_step(state, x, dt, A, B, C, D) -> Tuple[jax.Array, jax.Array]:
    """One token a lane: ``state`` [B,H,P,N] float32, ``x`` [B,H,P], ``dt``
    [B,H], ``B`` and ``C`` [B,G,N]. Returns ``(y [B,H,P] in x's type, the
    new state)``. Elementwise over the state plus one reduction over N: a
    step reads and writes every lane's state once, which is all it costs."""
    b, h, p = x.shape
    g, n = B.shape[1:]
    r = h // g
    dt = dt.astype(F32).reshape(b, g, r)
    xg = x.astype(F32).reshape(b, g, r, p)
    keep = jnp.exp(dt * A.astype(F32).reshape(g, r))
    new = (keep[..., None, None] * state.reshape(b, g, r, p, n)
           + (dt[..., None] * xg)[..., None]
           * B.astype(F32)[:, :, None, None, :])
    y = (new * C.astype(F32)[:, :, None, None, :]).sum(-1)
    y = y + xg * D.astype(F32).reshape(g, r)[:, :, None]
    return y.reshape(b, h, p).astype(x.dtype), new.reshape(b, h, p, n)


def causal_conv(x, w, bias, tail: Optional[jax.Array] = None):
    """Depthwise causal convolution over a sequence: ``x`` [B,S,C], ``w``
    [K,C] (tap K-1 is the token's own), ``bias`` [C]; the K-1 inputs
    before the first token are ``tail`` [B,K-1,C] (zeros where None).
    Returns the convolved [B,S,C] in x's type (float32 sums)."""
    k = w.shape[0]
    s = x.shape[1]
    if tail is None:
        tail = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    window = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    out = bias.astype(F32)
    for j in range(k):
        out = out + window[:, j:j + s].astype(F32) * w[j].astype(F32)
    return out.astype(x.dtype)
