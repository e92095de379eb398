"""Flash attention with a custom blockwise VJP.

A naively differentiated scan-based online-softmax saves every block's
scores as scan residuals - O(Sq x Sk) memory, defeating the whole point
(measured: 40 GiB f32 residual tensors on the llama4/train_4k cell).  This
module implements the FlashAttention backward recurrence explicitly
(Dao et al., arXiv:2205.14135): the forward saves only (q, k, v, o, lse),
and the backward recomputes per-block scores, so train-time attention
memory is O(S) + O(block^2).

Layout: q [B, Sq, KVH, G, dh]; k, v [B, Sk, KVH, dh]; GQA-native (no head
replication; the G axis rides along in the einsums).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import ordered_sum

NEG_INF = -1e30


def _blocked(x, n_blocks, block, axis=1):
    shape = x.shape[:axis] + (n_blocks, block) + x.shape[axis + 1:]
    return jnp.moveaxis(x.reshape(shape), axis, 0)


def _mask_penalty(qpos, kpos, causal, window, sk):
    """Additive f32 [bq, bk] penalty (0 or NEG_INF).  Kept 2-D and added to
    the scores so no [.., heads, ..] broadcast pred tensor is ever
    materialized (XLA hoists loop-invariant masks; a broadcast boolean costs
    O(nq*nk*b*h*g*bq*bk) bytes - measured 10 GiB on llama4/train_4k)."""
    kposf = kpos.astype(jnp.float32)
    m = kposf[None, :] < sk                      # padding
    if causal:
        cm = qpos[:, None] >= kposf[None, :]
        if window is not None:
            cm &= (qpos[:, None] - kposf[None, :]) < window
        m = m & cm
    return jnp.where(m, 0.0, NEG_INF).astype(jnp.float32)   # [bq, bk]


def _fwd_blocks(q, k, v, qpos0, *, causal, block_q, block_kv, window):
    b, sq, kvh, g, dh = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_kv
    scale = 1.0 / jnp.sqrt(dh)
    qb = _blocked(q, nq, block_q)                 # [nq, b, bq, kvh, g, dh]
    kb = _blocked(k, nk, block_kv)                # [nk, b, bk, kvh, dh]
    vb = _blocked(v, nk, block_kv)

    qpos_b = qpos0.reshape(nq, block_q)

    def q_step(_, inp):
        qi, qpos = inp

        def kv_step(carry, inp2):
            m_run, l_run, acc = carry
            ki, vi, ik = inp2
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qi.astype(jnp.float32),
                           ki.astype(jnp.float32)) * scale
            kpos = ik * block_kv + jnp.arange(block_kv)
            pen = _mask_penalty(qpos, kpos, causal, window, sk)
            s = s + pen[None, :, None, None, :]
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            corr = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new[..., None])
            # each block's sum in the stated order of the glue sums
            l_new = l_run * corr + ordered_sum(p)[..., 0]
            acc = acc * corr[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p, vi.astype(jnp.float32))
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, block_q, kvh, g), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, block_q, kvh, g), jnp.float32)
        a0 = jnp.zeros((b, block_q, kvh, g, dh), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (kb, vb, jnp.arange(nk)))
        l_safe = jnp.maximum(l_f, 1e-30)
        o = (acc / l_safe[..., None]).astype(q.dtype)
        lse = m_f + jnp.log(l_safe)
        return None, (o, lse)

    _, (ob, lseb) = jax.lax.scan(q_step, None, (qb, qpos_b))
    o = jnp.moveaxis(ob, 0, 1).reshape(b, sq, kvh, g, dh)
    lse = jnp.moveaxis(lseb, 0, 1).reshape(b, sq, kvh, g)
    return o, lse


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7)
)
def _flash(q, k, v, qpos0, causal, block_q, block_kv, window):
    o, _ = _fwd_blocks(q, k, v, qpos0, causal=causal,
                       block_q=block_q, block_kv=block_kv, window=window)
    return o


def _flash_fwd(q, k, v, qpos0, causal, block_q, block_kv, window):
    o, lse = _fwd_blocks(q, k, v, qpos0, causal=causal,
                         block_q=block_q, block_kv=block_kv, window=window)
    return o, (q, k, v, o, lse, qpos0)


def _flash_bwd(causal, block_q, block_kv, window, res, do):
    q, k, v, o, lse, qpos0 = res
    b, sq, kvh, g, dh = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_kv
    scale = 1.0 / jnp.sqrt(dh)
    # D_i = rowsum(dO * O)
    delta = jnp.einsum("bqhgd,bqhgd->bqhg", do.astype(jnp.float32),
                       o.astype(jnp.float32))
    qb = _blocked(q, nq, block_q)
    dob = _blocked(do, nq, block_q)
    lseb = _blocked(lse, nq, block_q)
    deltab = _blocked(delta, nq, block_q)
    qpos_b = qpos0.reshape(nq, block_q)
    kb = _blocked(k, nk, block_kv)
    vb = _blocked(v, nk, block_kv)

    def kv_step(dq_acc, inp):
        ki, vi, ik = inp
        kpos = ik * block_kv + jnp.arange(block_kv)

        def q_step(carry_q, inp2):
            dk_acc, dv_acc = carry_q
            qi, doi, lsei, di, qpos = inp2
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qi.astype(jnp.float32),
                           ki.astype(jnp.float32)) * scale
            pen = _mask_penalty(qpos, kpos, causal, window, sk)
            s = s + pen[None, :, None, None, :]
            p = jnp.exp(s - lsei[..., None])                     # [b,q,h,g,k]
            dp = jnp.einsum("bqhgd,bkhd->bqhgk", doi.astype(jnp.float32),
                            vi.astype(jnp.float32))
            ds = p * (dp - di[..., None]) * scale
            dv_acc = dv_acc + jnp.einsum(
                "bqhgk,bqhgd->bkhd", p, doi.astype(jnp.float32))
            dk_acc = dk_acc + jnp.einsum("bqhgk,bqhgd->bkhd", ds,
                                         qi.astype(jnp.float32))
            dq_i = jnp.einsum("bqhgk,bkhd->bqhgd", ds,
                              ki.astype(jnp.float32))
            return (dk_acc, dv_acc), dq_i

        dk0 = jnp.zeros((b, block_kv, kvh, dh), jnp.float32)
        dv0 = jnp.zeros((b, block_kv, kvh, dh), jnp.float32)
        (dk_i, dv_i), dq_blocks = jax.lax.scan(
            q_step, (dk0, dv0),
            (qb, dob, lseb, deltab, qpos_b))
        return dq_acc + dq_blocks, (dk_i, dv_i)

    dq0 = jnp.zeros((nq, b, block_q, kvh, g, dh), jnp.float32)
    dq, (dkb, dvb) = jax.lax.scan(
        kv_step, dq0, (kb, vb, jnp.arange(nk)))
    dq = jnp.moveaxis(dq, 0, 1).reshape(b, sq, kvh, g, dh).astype(q.dtype)
    dk = jnp.moveaxis(dkb, 0, 1).reshape(b, sk, kvh, dh).astype(k.dtype)
    dv = jnp.moveaxis(dvb, 0, 1).reshape(b, sk, kvh, dh).astype(v.dtype)
    return dq, dk, dv, jnp.zeros_like(qpos0)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal=True, q_offset=0, block_q=256,
                    block_kv=512, window: Optional[int] = None):
    """Memory-O(S) attention with flash custom VJP.

    q: [B, Sq, KVH, G, dh]; k, v: [B, Sk, KVH, dh] -> [B, Sq, KVH, G, dh]
    """
    b, sq, kvh, g, dh = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_kv = min(block_kv, sk)
    pq = (-sq) % block_q
    pk = (-sk) % block_kv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    qpos0 = (jnp.arange(sq + pq, dtype=jnp.float32) + q_offset)
    o = _flash(q, k, v, qpos0, causal, block_q, block_kv, window)
    return o[:, :sq]


def flash_attention_cp(q, k, v, *, causal=True, block_q=256, block_kv=512,
                       window=None):
    """Context-parallel flash attention: the q-sequence axis shards over the
    ``model`` mesh axis via shard_map; k/v are replicated (they already are
    for every arch whose head count does not divide the mesh axis - 24/28/40
    heads vs 16).  Forward needs ZERO collectives; backward psums dk/dv over
    the model axis (inserted by the shard_map transpose).  This is the §Perf
    fix for head-indivisible architectures, where plain GSPMD replicates the
    whole attention computation and round-trips q through all-gathers."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shd

    mesh = shd.get_mesh()
    b, sq, kvh, g, dh = q.shape
    if mesh is None or "model" not in mesh.axis_names:
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_kv=block_kv, window=window)
    n_model = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    s_loc = sq // n_model if sq % n_model == 0 else 0
    if not s_loc:
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_kv=block_kv, window=window)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names) \
        or None

    def body(q_loc, k_full, v_full):
        idx = jax.lax.axis_index("model")
        bq = min(block_q, s_loc)
        bk = min(block_kv, k_full.shape[1])
        pq = (-s_loc) % bq
        ql = q_loc
        if pq:
            ql = jnp.pad(ql, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
        pk = (-k_full.shape[1]) % bk
        kl, vl = k_full, v_full
        if pk:
            kl = jnp.pad(kl, ((0, 0), (0, pk), (0, 0), (0, 0)))
            vl = jnp.pad(vl, ((0, 0), (0, pk), (0, 0), (0, 0)))
        qpos = (idx * s_loc + jnp.arange(s_loc + pq)).astype(jnp.float32)
        o = _flash(ql, kl, vl, qpos, causal, bq, bk, window)
        return o[:, :s_loc]

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(
            P(batch_axes, "model", None, None, None),
            P(batch_axes, None, None, None),
            P(batch_axes, None, None, None),
        ),
        out_specs=P(batch_axes, "model", None, None, None),
        check_vma=False,
    )
    return fn(q, k, v)
