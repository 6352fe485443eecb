"""Distributed (mesh-level) LeanAttention: sequence-parallel decode.

The paper's multi-GPU story (§III-D, Fig. 9) shards attention across devices
and relies on the associative re-scaling reduction to combine partial
outputs. On a TPU mesh this is expressed natively:

  * KV cache sharded along the *sequence* dimension over a mesh axis
    (each device owns an equal LeanTile range — the stream-K partition at
    device granularity),
  * every device computes an un-scaled partial (o, m, l) over its local KV
    chunk,
  * the merge runs as three collectives: ``pmax`` for m, and ``psum`` for the
    re-scaled l and o. This *is* the associative operator evaluated as a
    reduction tree by the ICI network.

Used by `serve_step` for the ``long_500k`` shape (batch=1: batch/head
parallelism alone cannot fill the mesh — exactly the regime the paper
targets).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

import numpy as np
from jax.sharding import PartitionSpec as P

from .merge import AttnPartial
from .attention import chunk_partial


def lean_merge_collective(part: AttnPartial, axis_name: str) -> jax.Array:
    """Reduce partial triples across a mesh axis and finalize.

    Exactness follows from associativity (paper §IV-A): pmax/psum evaluate
    the same operator as any sequential merge order.
    """
    m_glob = jax.lax.pmax(part.m, axis_name)
    safe = jnp.where(jnp.isinf(m_glob) & (m_glob < 0), 0.0, m_glob)
    scale = jnp.where(
        jnp.isinf(part.m) & (part.m < 0), 0.0, jnp.exp(part.m - safe)
    )
    l_glob = jax.lax.psum(scale * part.l, axis_name)
    o_glob = jax.lax.psum(scale[..., None] * part.o, axis_name)
    return o_glob / l_glob[..., None]


def sp_decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: jax.sharding.Mesh,
    seq_axis="data",
    head_axis: Optional[str] = "model",
    batch_axis=None,
    ctx_len: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Sequence-parallel exact decode attention over a mesh.

    q: (B, Hq, d); k, v: (B, Hkv, S, d) sharded along S over ``seq_axis``
    (a name or tuple of names — e.g. ('data','model') shards the context
    256-way for batch=1 long-context decode, the paper's Fig. 9 regime).
    ``batch_axis`` optionally shards B. Heads shard over ``head_axis`` only
    when both Hq and Hkv divide it (GQA co-location); else they replicate
    and the sequence axes carry the parallelism. The cross-device reduction
    is the associative softmax re-scaling merge (pmax+psum).
    """
    B, Hq, d = q.shape
    _, Hkv, S, _ = k.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    seq_axes = (seq_axis,) if isinstance(seq_axis, str) else tuple(seq_axis)
    seq_axes = tuple(a for a in seq_axes if a in mesh.axis_names)
    n_seq = int(np.prod([mesh.shape[a] for a in seq_axes]))
    if S % n_seq:
        raise ValueError(f"S={S} must divide over seq axes {seq_axes}")

    both = (
        head_axis
        and head_axis not in seq_axes
        and head_axis != batch_axis
        and Hq % mesh.shape[head_axis] == 0
        and Hkv % mesh.shape[head_axis] == 0
    )
    h_spec = head_axis if both else None
    b_spec = batch_axis if (batch_axis and B % mesh.shape.get(batch_axis, 1) == 0 and B >= mesh.shape.get(batch_axis, 1)) else None

    def local(q_l, k_l, v_l, ctx_l):
        # absolute offset of this device's KV chunk
        idx = jax.lax.axis_index(seq_axes if len(seq_axes) > 1 else seq_axes[0])
        chunk = k_l.shape[2]
        offset = idx * chunk
        b, hkv = k_l.shape[0], k_l.shape[1]
        qg = q_l.reshape(b, hkv, -1, d)
        valid = jnp.clip(ctx_l - offset, 0, chunk)            # (B,)
        vlen = valid[:, None, None, None]                     # vs s (b,h,g,t)
        part = chunk_partial(qg, k_l, v_l, scale, valid_len=vlen)
        out = lean_merge_collective(
            part, seq_axes if len(seq_axes) > 1 else seq_axes[0]
        )
        return out.reshape(b, -1, d).astype(q_l.dtype)

    in_specs = (
        P(b_spec, h_spec, None),
        P(b_spec, h_spec, seq_axes if len(seq_axes) > 1 else seq_axes[0], None),
        P(b_spec, h_spec, seq_axes if len(seq_axes) > 1 else seq_axes[0], None),
        P(b_spec),
    )
    out_specs = P(b_spec, h_spec, None)
    fn = jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    if ctx_len is None:
        ctx_len = jnp.full((B,), S, dtype=jnp.int32)
    return fn(q, k, v, ctx_len)
