"""The gated delta rule with a decay a channel (Kimi Delta Attention's
recurrence), computed in chunks in ``jax.numpy``: no Pallas kernel.

For each head, with ``S_0 = 0`` and the state ``S in R^{d_k x d_v}``::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,        alpha_t = exp(g_t) in (0, 1]^{d_k}

Chunked form (Gated DeltaNet, arXiv:2412.06464; Kimi Linear,
arXiv:2510.26692). Positions fall in chunks of :data:`CHUNK`; inside one,
``G`` is the running sum of ``g`` from the chunk's start and ``S_0`` the
state at its start. A step's write ``u_t`` solves a unit lower-triangular
system (the WY/UT form)::

    (I + Diag(beta) A) U = Diag(beta) (V - (K * e^G) S_0),
    A_tj = sum_c k_tc k_jc e^(G_tc - G_jc)      (j < t)

so ``U = U' - W S_0`` with ``U' = M^-1 Diag(beta) V`` and ``W = M^-1
Diag(beta) (K * e^G)``; then ``o = (Q * e^G) S_0 + P U`` with ``P_tj = sum_c
q_tc k_jc e^(G_tc - G_jc)`` (``j <= t``) and the next chunk's state is
``e^(G_C) * S_0 + (K * e^(G_C - G))^T U``. Everything but the two-product
step ``U = U' - W S_0``, ``S' = e^(G_C) * S_0 + K^T U`` is the same for
every chunk and is made for all of them at once; that step is a scan.

Every decay is ``exp`` of a difference of running sums that is at most 0,
or, on a diagonal block of :data:`SUB` positions, at most ``SUB / 2`` steps
of ``g`` from the block's middle: with ``g >= LOWER`` a step that is 40 at
most either way, so nothing overflows float32 and an exponent from the
chunk's start (``e^(-G)`` reaches ``e^320``) is never formed. The state,
the running sums and every product inside a chunk are float32, the products
at ``HIGHEST`` precision.

The backward (``custom_vjp``) keeps the inputs and the state at each chunk's
start, and makes the rest again: the chunks' interior in parallel, the
state's cotangent by a reverse scan of two products a chunk. The interior
is made, and differentiated, :data:`HEADS_A_PASS` heads at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64              # positions a chunk
SUB = 16                # positions of a diagonal block inside a chunk
LOWER = -5.0            # the least log-decay a step that the form admits
# heads whose chunks' interior is made, or differentiated, in one pass: the
# interior's temporaries are the rule's largest, so they are made a group
# of heads at a time (a layer's forward and backward at 32 heads of 128 and
# 4,096 positions: 1.96 GB of temporaries all at once, 1.27 GB by 8, the
# compiler's account for a v5e)
HEADS_A_PASS = 8

_HI = lax.Precision.HIGHEST


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def _pairs(a, b, G, strict: bool):
    """``[..., C, C]``: ``sum_c a_tc b_jc e^(G_tc - G_jc)`` for ``j < t``
    (``j <= t`` unless ``strict``), 0 above. Off the diagonal blocks the
    decay is split at the row block's first position ``r``, ``e^(G_t - G_r)
    e^(G_r - G_j)``, both factors at most 1; on a diagonal block at its
    middle position, both at most ``e^(SUB / 2 * |LOWER|)``."""
    C = a.shape[-2]
    rows = []
    for start in range(0, C, SUB):
        end, mid = start + SUB, start + SUB // 2
        ga, gm = G[..., start:end, :], G[..., mid:mid + 1, :]
        diag = _dot("...td,...jd->...tj", a[..., start:end, :]
                    * jnp.exp(ga - gm), b[..., start:end, :]
                    * jnp.exp(gm - ga))
        t = jnp.arange(SUB)
        seen = t[:, None] > t[None, :] if strict \
            else t[:, None] >= t[None, :]
        block = [jnp.where(seen, diag, 0.0)]
        if start:
            gr = G[..., start:start + 1, :]
            block.insert(0, _dot(
                "...td,...jd->...tj", a[..., start:end, :] * jnp.exp(ga - gr),
                b[..., :start, :] * jnp.exp(gr - G[..., :start, :])))
        if end < C:
            block.append(jnp.zeros(diag.shape[:-1] + (C - end,), diag.dtype))
        rows.append(jnp.concatenate(block, -1))
    return jnp.concatenate(rows, -2)


def _intra(q, k, v, g, beta):
    """What a chunk's output and next state need beside its starting state,
    for every chunk at once: ``[..., C, d]`` inputs (``beta [..., C]``) to
    ``(Q e^G, K e^(G_C - G), e^(G_C), W, U', P)``."""
    G = jnp.cumsum(g, axis=-2)
    last = G[..., -1:, :]
    tri = jnp.eye(q.shape[-2], dtype=jnp.float32) \
        + beta[..., None] * _pairs(k, k, G, strict=True)
    rhs = beta[..., None] * jnp.concatenate([k * jnp.exp(G), v], -1)
    wu = lax.linalg.triangular_solve(tri, rhs, left_side=True, lower=True,
                                     unit_diagonal=True)
    dk = k.shape[-1]
    return (q * jnp.exp(G), k * jnp.exp(last - G), jnp.exp(last[..., 0, :]),
            wu[..., :dk], wu[..., dk:], _pairs(q, k, G, strict=False))


def _by_heads(fn, *xs):
    """``fn`` over groups of :data:`HEADS_A_PASS` heads (axis 2 of every
    input and output), one group after another."""
    H = xs[0].shape[2]
    n = H // math.gcd(H, HEADS_A_PASS)
    if n == 1:
        return fn(*xs)

    def split(x):
        x = x.reshape(*x.shape[:2], n, H // n, *x.shape[3:])
        return jnp.moveaxis(x, 2, 0)

    def join(x):
        x = jnp.moveaxis(x, 0, 2)
        return x.reshape(*x.shape[:2], H, *x.shape[4:])

    out = lax.map(lambda xs: fn(*xs), tuple(split(x) for x in xs))
    return jax.tree_util.tree_map(join, out)


def _states(w, u0, kd, decay):
    """The state at each chunk's start and each chunk's writes, by a scan
    over the chunks (leading axis of the ``[N, ...]`` inputs)."""
    def step(s, x):
        w, u0, kd, decay = x
        u = u0 - _dot("...cd,...de->...ce", w, s)
        return decay[..., None] * s + _dot("...cd,...ce->...de", kd, u), \
            (s, u)

    B = w.shape[1:-2]
    s0 = jnp.zeros(B + (w.shape[-1], u0.shape[-1]), jnp.float32)
    _, (starts, u) = lax.scan(step, s0, (w, u0, kd, decay))
    return starts, u


def _chunked(*xs):
    """``[B, T, H, ...]`` to float32 ``[N, B, H, C, ...]``, zeros past
    ``T``: a padded position writes nothing (``beta = 0``) and decays
    nothing (``g = 0``)."""
    def cut(x):
        x = jnp.pad(x.astype(jnp.float32), [(0, 0), (0, -x.shape[1] % CHUNK)]
                    + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(x.shape[0], -1, CHUNK, *x.shape[2:])
        return jnp.moveaxis(x, (1, 2), (0, 3))
    return tuple(cut(x) for x in xs)


def _unchunked(x, T):
    """``[N, B, H, C, ...]`` to ``[B, T, H, ...]``."""
    x = jnp.moveaxis(x, (0, 3), (1, 2))
    return x.reshape(x.shape[0], -1, *x.shape[3:])[:, :T]


@jax.custom_vjp
def delta_rule(q, k, v, g, beta):
    """``o [B, T, H, d_v]`` float32 of the gated delta rule over ``q, k
    [B, T, H, d_k]``, ``v [B, T, H, d_v]``, the log-decay ``g [B, T, H,
    d_k]`` (in ``[LOWER, 0]``) and the write strength ``beta [B, T, H]``,
    from a zero state. Normalising q and k, and scaling q, is the
    caller's."""
    return _forward(q, k, v, g, beta)[0]


def _forward(q, k, v, g, beta):
    with jax.named_scope("hvd_kda_scan"):
        T = q.shape[1]
        qd, kd, decay, w, u0, p = _by_heads(_intra,
                                            *_chunked(q, k, v, g, beta))
        starts, u = _states(w, u0, kd, decay)
        o = _dot("...cd,...de->...ce", qd, starts) \
            + _dot("...tj,...je->...te", p, u)
        return _unchunked(o, T), starts


def _delta_rule_fwd(q, k, v, g, beta):
    o, starts = _forward(q, k, v, g, beta)
    return o, (q, k, v, g, beta, starts)


def _delta_rule_bwd(res, do):
    q, k, v, g, beta, starts = res
    with jax.named_scope("hvd_kda_scan"):
        T = q.shape[1]
        chunks = _chunked(q, k, v, g, beta) + _chunked(do)

        def sent_back(q, k, v, g, beta, do):
            """What the state's reverse scan reads of a chunk: ``K e^(G_C -
            G)``, ``W``, ``e^(G_C)``, and what the chunk's output sends back
            to its writes (``P^T dO``) and to its starting state (``(Q
            e^G)^T dO``)."""
            qd, kd, decay, w, _, p = _intra(q, k, v, g, beta)
            return kd, w, decay, _dot("...tj,...te->...je", p, do), \
                _dot("...cd,...ce->...de", qd, do)

        def step(ds, x):
            kd, w, decay, du_out, ds_out = x
            du = du_out + _dot("...cd,...de->...ce", kd, ds)
            return ds_out + decay[..., None] * ds \
                - _dot("...cd,...ce->...de", w, du), (ds, du)

        _, (ds_next, du) = lax.scan(
            step, jnp.zeros_like(starts[0]), _by_heads(sent_back, *chunks),
            reverse=True)

        def pulled(q, k, v, g, beta, do, starts, ds_next, du):
            """The chunks' interior made again and differentiated, with the
            cotangents of its outputs."""
            (qd, kd, decay, w, u0, p), pull = jax.vjp(_intra, q, k, v, g,
                                                      beta)
            u = u0 - _dot("...cd,...de->...ce", w, starts)
            seen = jnp.arange(CHUNK)
            return pull((
                _dot("...te,...de->...td", do, starts),
                _dot("...ce,...de->...cd", u, ds_next),
                (ds_next * starts).sum(-1),
                -_dot("...ce,...de->...cd", du, starts),
                du,
                jnp.where(seen[:, None] >= seen[None, :],
                          _dot("...te,...je->...tj", do, u), 0.0)))

        grads = _by_heads(pulled, *chunks, starts, ds_next, du)
        return tuple(_unchunked(d, T).astype(x.dtype)
                     for d, x in zip(grads, (q, k, v, g, beta)))


delta_rule.defvjp(_delta_rule_fwd, _delta_rule_bwd)

