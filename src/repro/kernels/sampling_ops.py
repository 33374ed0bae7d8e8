"""Device-native sampling kernels: fanout selection + layout build on device.

The host ``FanoutSampler`` ranks every candidate in-edge of the frontier by a
counter-based hash key and keeps the ``fanout[etype]`` smallest per
(destination, etype) bin. This module evaluates the *same* selection as two
jit-compiled stages over the device-resident CSC (``core.graph.DeviceGraph``),
with every shape static so steady-state sampling never retraces:

* **stage A** (``make_sample_hop``): per frontier node × etype, enumerate the
  CSC candidate window ``[Fp, R, C]`` (C = the graph's max per-(dst, etype)
  in-degree), key it with ``edge_sample_keys`` (identical positions, identical
  keys as the host — the parity contract), and keep the K smallest keys per
  bin via a stable argsort; also emit the sorted frontier∪sources union and a
  3-vector of (next-frontier, edge, unique-pair) counts — the only values the
  host reads back, to pick the next stage's static bucket.

* **stage B** (``make_build_block``): fixed-shape compaction of the union
  into the block's sorted-unique node set, canonical etype-sorted edge arrays
  with all ``HeteroGraph`` products (dst-CSR, compact-materialization map),
  and the complete ``KernelLayouts`` pytree via the ``device_*`` builders in
  ``kernels/layout.py`` — the device replacement for the loader's host-side
  ``build_minibatch`` layout pass.

Padding discipline: pad nodes sort after real nodes (sentinel id = N), pad
edges carry etype R-1 and connect the first pad node to itself, so every
type-sorted invariant the kernels rely on (non-decreasing etype/ntype/dst,
tile-to-group maps) holds by construction and pad rows only ever feed pad
rows.

The candidate-key generation also has a Pallas formulation
(``candidate_keys``): it is the one stage that is pure elementwise math over
a tile-regular ``[rows, C]`` window, so it maps onto a trivial VMEM-blocked
kernel; selection/compaction stay XLA (sorts and scatters, which Pallas TPU
has no primitive advantage for).
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codegen import KernelLayouts
from repro.core.graph import DeviceGraph, GraphTensors
from repro.kernels import layout as L
from repro.kernels import ops as K
from repro.sampling.sampler import FULL_NEIGHBORHOOD, edge_sample_keys, mix32

_U32_MAX = np.uint32(0xFFFFFFFF)
_I32_MAX = np.int32(2**31 - 1)


def effective_fanouts(fanout: np.ndarray, max_bin: int) -> Tuple[int, ...]:
    """Resolve a per-etype fanout vector against the candidate window width:
    ``FULL_NEIGHBORHOOD`` (and any cap beyond the widest bin) becomes C —
    no bin has more than C candidates, so keeping C keys is exact."""
    c = max(1, int(max_bin))
    return tuple(c if int(k) == FULL_NEIGHBORHOOD else min(int(k), c)
                 for k in fanout)


# ---------------------------------------------------------------------------
# candidate keys (XLA + Pallas formulations)
# ---------------------------------------------------------------------------
def _keys_kernel(base_ref, start_ref, cnt_ref, out_ref):
    col = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    pos = start_ref[...] + col                      # [tile_rows, C]
    keys = mix32(pos.astype(jnp.uint32) ^ base_ref[0])
    out_ref[...] = jnp.where(col < cnt_ref[...], keys, _U32_MAX)


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def _candidate_keys_pallas(starts2, cnts2, base_arr, *, width, interpret):
    rows = starts2.shape[0]
    tile_rows = 8 if rows % 8 == 0 else 1
    return pl.pallas_call(
        _keys_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // tile_rows,),
            in_specs=[
                pl.BlockSpec((tile_rows, 1), lambda i, base: (i, 0)),
                pl.BlockSpec((tile_rows, 1), lambda i, base: (i, 0)),
            ],
            out_specs=pl.BlockSpec((tile_rows, width), lambda i, base: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, width), jnp.uint32),
        name="_candidate_keys_pallas",
        interpret=interpret,
    )(base_arr, starts2, cnts2)


def candidate_keys(starts: jnp.ndarray, cnts: jnp.ndarray, base_key,
                   width: int, backend: str = "xla") -> jnp.ndarray:
    """Masked per-candidate sort keys over the CSC windows.

    ``starts``/``cnts`` are ``[..., 1]``-broadcastable bin starts and sizes
    (any leading shape); returns ``[..., width]`` uint32 keys, invalid
    candidates pinned to ``0xFFFFFFFF`` so they sort last.
    """
    if backend == "xla":
        col = jnp.arange(width, dtype=jnp.int32)
        pos = starts[..., None] + col
        keys = edge_sample_keys(base_key, pos)
        return jnp.where(col < cnts[..., None], keys, _U32_MAX)
    lead = starts.shape
    base_arr = jnp.asarray(base_key, jnp.uint32).reshape(1)
    out = _candidate_keys_pallas(
        starts.reshape(-1, 1), cnts.reshape(-1, 1), base_arr,
        width=width, interpret=(backend == "pallas_interpret"))
    return out.reshape(*lead, width)


# ---------------------------------------------------------------------------
# stage A: per-hop fanout selection
# ---------------------------------------------------------------------------
def make_sample_hop(dg: DeviceGraph, k_eff: Sequence[int], fp: int,
                    backend: str = "xla"):
    """Build the traceable stage-A function for one (frontier bucket, hop
    fanout) configuration.

    ``fn(csc_indptr, csc_src, frontier [fp], base_key) ->
    (union_sorted, sel_src [fp,R,K], sel_valid [fp,R,K], counts [3])`` where
    ``counts = (next-frontier nodes, sampled edges, unique (src,etype)
    pairs)`` — the only device->host readback of the sampling loop.
    """
    n, r = dg.num_nodes, dg.num_etypes
    e = dg.num_edges
    c = max(1, dg.max_bin)
    kvec = tuple(int(k) for k in k_eff)
    kmax = max(1, max(kvec)) if kvec else 1
    if e == 0:
        raise ValueError("device sampling needs a graph with edges")

    def fn(csc_indptr, csc_src, frontier, base_key):
        f = jnp.clip(frontier, 0, n - 1)
        fvalid = frontier < n
        bins = f[:, None] * r + jnp.arange(r, dtype=jnp.int32)[None, :]
        start = csc_indptr[bins]                      # [fp, R]
        cnt = jnp.where(fvalid[:, None], csc_indptr[bins + 1] - start, 0)
        keys = candidate_keys(start, cnt, base_key, c, backend)  # [fp,R,C]
        order = jnp.argsort(keys, axis=-1)[..., :kmax]  # stable: ties by pos
        sel_pos = jnp.take_along_axis(
            start[..., None] + jnp.arange(c, dtype=jnp.int32), order, axis=-1)
        cap = jnp.minimum(cnt, jnp.asarray(kvec, jnp.int32)[None, :])
        sel_valid = jnp.arange(kmax, dtype=jnp.int32) < cap[..., None]
        sel_src = jnp.where(
            sel_valid, csc_src[jnp.clip(sel_pos, 0, e - 1)], n)
        e_cnt = sel_valid.sum(dtype=jnp.int32)

        union = jnp.sort(jnp.concatenate([frontier, sel_src.reshape(-1)]))
        fresh = jnp.concatenate(
            [jnp.ones(1, bool), union[1:] != union[:-1]])
        n_next = ((union < n) & fresh).sum(dtype=jnp.int32)

        pair = jnp.where(sel_valid,
                         sel_src * r + jnp.arange(r, dtype=jnp.int32)[:, None],
                         _I32_MAX)
        sp = jnp.sort(pair.reshape(-1))
        ufresh = jnp.concatenate([jnp.ones(1, bool), sp[1:] != sp[:-1]])
        u_cnt = ((sp < _I32_MAX) & ufresh).sum(dtype=jnp.int32)

        counts = jnp.stack([n_next, e_cnt, u_cnt])
        return union, sel_src, sel_valid, counts

    return fn


# ---------------------------------------------------------------------------
# stage B: block compaction + graph products + kernel layouts
# ---------------------------------------------------------------------------
def make_build_block(dg: DeviceGraph, fp: int, kmax: int, n_pad: int,
                     e_pad: int, u_pad: int, tile: int, node_block: int):
    """Build the traceable stage-B function for one bucket tuple.

    ``fn(union_sorted, sel_src, sel_valid, frontier, node_type) ->
    (GraphTensors, KernelLayouts, node_ids [n_pad], dst_local [fp],
    input_gather [n_pad])`` — a complete device-built block: the exact
    pytrees ``build_minibatch`` produces on the host, with static shapes
    derived from the bucket (``n_pad``/``e_pad``/``u_pad`` are pow2 buckets
    of the stage-A counts; layout row capacities add one worst-case pad tile
    per group so the device ``pad_segments``/``block_csr`` always fit).
    """
    n, r, t = dg.num_nodes, dg.num_etypes, dg.num_ntypes
    for name, v in (("n_pad", n_pad), ("e_pad", e_pad), ("u_pad", u_pad)):
        if v % tile:
            raise ValueError(f"{name}={v} must be a tile multiple")
    nb = (n_pad + node_block - 1) // node_block
    rp_e, rp_u, rp_n = e_pad + r * tile, u_pad + r * tile, n_pad + t * tile
    ep_csr = e_pad + nb * tile
    lf = fp * r * kmax

    def fn(union, sel_src, sel_valid, frontier, node_type_g):
        # ---- node compaction: sorted unique reals, then sentinel pads ----
        fresh = jnp.concatenate([jnp.ones(1, bool), union[1:] != union[:-1]])
        fo = (union < n) & fresh
        rank = jnp.cumsum(fo).astype(jnp.int32) - 1
        n_cnt = fo.sum(dtype=jnp.int32)
        node_ids = jnp.full(n_pad, n, jnp.int32).at[
            jnp.where(fo, rank, n_pad)].set(union, mode="drop")
        node_type = jnp.where(
            node_ids < n, node_type_g[jnp.clip(node_ids, 0, n - 1)], t - 1
        ).astype(jnp.int32)
        ntype_ptr = jnp.concatenate([
            jnp.zeros(1, jnp.int32),
            jnp.cumsum(jnp.zeros(t, jnp.int32).at[node_type].add(1)),
        ]).astype(jnp.int32)

        # ---- edges: localize, canonical etype sort, pad tail ----
        flat_valid = sel_valid.reshape(lf)
        src_g = jnp.where(flat_valid, sel_src.reshape(lf), n)
        dst_g = jnp.where(
            flat_valid,
            jnp.broadcast_to(frontier[:, None, None],
                             (fp, r, kmax)).reshape(lf), n)
        et_f = jnp.broadcast_to(
            jnp.arange(r, dtype=jnp.int32)[None, :, None],
            (fp, r, kmax)).reshape(lf)
        src_l = jnp.searchsorted(node_ids, src_g).astype(jnp.int32)
        dst_l = jnp.searchsorted(node_ids, dst_g).astype(jnp.int32)
        sortkey = jnp.where(flat_valid, et_f, r)
        order = jnp.argsort(sortkey)            # stable: valid first, by et
        e_cnt = flat_valid.sum(dtype=jnp.int32)
        posn = jnp.arange(lf, dtype=jnp.int32)
        dest = jnp.where(posn < e_cnt, posn, e_pad)
        in_range = jnp.arange(e_pad, dtype=jnp.int32) < e_cnt
        # pad edges: first pad node -> itself, etype R-1 (keeps every
        # type-sorted invariant; never read back through the gathers)
        src_c = jnp.where(
            in_range,
            jnp.zeros(e_pad, jnp.int32).at[dest].set(src_l[order],
                                                     mode="drop"), n_cnt)
        dst_c = jnp.where(
            in_range,
            jnp.zeros(e_pad, jnp.int32).at[dest].set(dst_l[order],
                                                     mode="drop"), n_cnt)
        et_c = jnp.where(
            in_range,
            jnp.zeros(e_pad, jnp.int32).at[dest].set(
                sortkey[order].astype(jnp.int32), mode="drop"), r - 1)
        etype_ptr = jnp.concatenate([
            jnp.zeros(1, jnp.int32),
            jnp.cumsum(jnp.zeros(r, jnp.int32).at[et_c].add(1)),
        ]).astype(jnp.int32)

        # ---- destination-sorted view ----
        perm_dst = jnp.argsort(dst_c).astype(jnp.int32)     # stable
        dst_sorted = dst_c[perm_dst]
        dst_ptr = jnp.concatenate([
            jnp.zeros(1, jnp.int32),
            jnp.cumsum(jnp.zeros(n_pad, jnp.int32).at[dst_c].add(1)),
        ]).astype(jnp.int32)

        # ---- compact materialization map (unique (src, etype) pairs) ----
        ukey = et_c * n_pad + src_c          # etype-major, pad pair largest
        sk = jnp.sort(ukey)
        ufresh = jnp.concatenate([jnp.ones(1, bool), sk[1:] != sk[:-1]])
        urank = jnp.cumsum(ufresh).astype(jnp.int32) - 1
        u_tot = ufresh.sum(dtype=jnp.int32)
        padkey = (r - 1) * n_pad + n_cnt
        ukeys = jnp.where(
            jnp.arange(u_pad, dtype=jnp.int32) < u_tot,
            jnp.zeros(u_pad, jnp.int32).at[
                jnp.where(ufresh, urank, u_pad)].set(sk, mode="drop"),
            padkey)
        unique_etype = (ukeys // n_pad).astype(jnp.int32)
        unique_src = (ukeys % n_pad).astype(jnp.int32)
        unique_etype_ptr = jnp.concatenate([
            jnp.zeros(1, jnp.int32),
            jnp.cumsum(jnp.zeros(r, jnp.int32).at[unique_etype].add(1)),
        ]).astype(jnp.int32)
        edge_to_unique = jnp.searchsorted(ukeys, ukey).astype(jnp.int32)

        gt = GraphTensors(
            src=src_c, dst=dst_c, etype=et_c, etype_ptr=etype_ptr,
            node_type=node_type, ntype_ptr=ntype_ptr, perm_dst=perm_dst,
            dst_sorted=dst_sorted, dst_ptr=dst_ptr, unique_src=unique_src,
            unique_etype=unique_etype, unique_etype_ptr=unique_etype_ptr,
            edge_to_unique=edge_to_unique,
            num_nodes=n_pad, num_ntypes=t, num_etypes=r,
        )

        # ---- kernel layouts, entirely on device ----
        e_rm, e_inv, e_t2g = L.device_pad_segments(etype_ptr, et_c, tile,
                                                   rp_e)
        u_rm, u_inv, u_t2g = L.device_pad_segments(
            unique_etype_ptr, unique_etype, tile, rp_u)
        n_rm, n_inv, n_t2g = L.device_pad_segments(ntype_ptr, node_type,
                                                   tile, rp_n)
        em_d, local_dst, t2b = L.device_block_csr(
            dst_ptr, dst_sorted, tile, node_block, ep_csr)
        edge_map = jnp.where(em_d >= 0, perm_dst[jnp.maximum(em_d, 0)], -1)
        edge_map_u = jnp.where(
            edge_map >= 0, edge_to_unique[jnp.maximum(edge_map, 0)], -1)
        kl = KernelLayouts(
            edge_seg=K.PaddedSegmentsDev(e_rm, e_inv, e_t2g, tile, r),
            unique_seg=K.PaddedSegmentsDev(u_rm, u_inv, u_t2g, tile, r),
            node_seg=K.PaddedSegmentsDev(n_rm, n_inv, n_t2g, tile, t),
            blocked=K.BlockedCSRDev(
                edge_map=edge_map, edge_map_unique=edge_map_u,
                local_dst=local_dst.reshape(-1, tile), t2b=t2b,
                edge_tile=tile, node_block=node_block,
                num_node_blocks=nb, num_nodes=n_pad),
            edge_src_rows=L.device_compose_gather_rows(e_rm, src_c),
            edge_dst_rows=L.device_compose_gather_rows(e_rm, dst_c),
            unique_src_rows=L.device_compose_gather_rows(u_rm, unique_src),
            dst_deg=(dst_ptr[1:] - dst_ptr[:-1]).astype(jnp.float32),
        )

        dst_local = jnp.searchsorted(node_ids, frontier).astype(jnp.int32)
        input_gather = jnp.where(node_ids < n, node_ids, 0)
        return gt, kl, node_ids, dst_local, input_gather

    return fn


# ---------------------------------------------------------------------------
# seed preparation (sorted-unique frontier, fixed shape, no readback)
# ---------------------------------------------------------------------------
def make_prep_seeds(num_nodes: int, fp: int):
    """``fn(seeds [B]) -> (frontier [fp], seed_perm [B])``: the sorted unique
    seed frontier (sentinel-padded) and each seed's row in it — the device
    mirror of the host's ``np.unique`` + ``searchsorted`` seed prologue."""

    def fn(seeds):
        su = jnp.sort(seeds)
        fo = jnp.concatenate([jnp.ones(1, bool), su[1:] != su[:-1]])
        rank = jnp.cumsum(fo).astype(jnp.int32) - 1
        frontier = jnp.full(fp, num_nodes, jnp.int32).at[
            jnp.where(fo, rank, fp)].set(su, mode="drop")
        seed_perm = jnp.searchsorted(frontier, seeds).astype(jnp.int32)
        return frontier, seed_perm

    return fn
