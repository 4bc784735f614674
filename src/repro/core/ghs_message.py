"""Paper-faithful message-driven GHS engine (Mazeev et al. 2016).

Executes the original GHS vertex procedures (Gallager–Humblet–Spira 1983,
handlers (1)-(11)) under the paper's implementation scheme (§3.2):

    While (True) {
        read_msgs();                   -> ingest()       (vectorized)
        process_queue();               -> sequential pop/dispatch loop
        [every CHECK_FREQUENCY steps]  -> drain the separate Test queue (C1)
        send_all_bufs();               -> flush() + all_to_all  (C4)
        check_finish();                -> psum silence detection (C5)
    }

Each MPI process of the paper maps to one device shard (shard_map over axis
"x"); vertices are block-distributed; per-destination aggregation buffers map
to fixed-capacity buckets exchanged with ONE fused all_to_all per superstep.
Messages are bit-packed uint32 lanes (C3); incoming messages locate their edge
via the linear-probe hash (C2) or the linear/binary-search ablations.  Under
the hash variant the whole inbox is edge-resolved in ONE vectorized probe
sweep (the ``kernels/edge_hash`` batched op) before the sequential dispatch
loop; resolved positions ride a side-lane of the local queue rings.

The superstep loop itself is device-resident (DESIGN.md §6): a
``jax.lax.while_loop`` advances up to ``check_frequency`` supersteps per
dispatch, counting consecutive silent psum checks on device
(``empty_iter_cnt_to_break``, paper §3.6), so the host synchronizes once per
interval — not twice per superstep as the legacy driver
(``params.round_loop == "host"``, retained as the before/after baseline)
does.  Both drivers run through :mod:`repro.core.runtime`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.core import runtime
from repro.core.ghs_state import (
    ACCEPT, BASIC, BRANCH, CHANGE_CORE, CONNECT, FIND, FOUND, INITIATE,
    POS_UNRESOLVED, REJECT, REJECTED, REPORT, TEST, GHSTopology, ShardState,
    hash_slot, init_shards, stack_shards,
)
from repro.core.graph import Graph
from repro.core.kruskal_ref import ForestResult
from repro.core.params import DEFAULT_PARAMS, GHSParams
from repro.kernels.edge_hash import ops as edge_ops

INF32 = jnp.uint32(0xFFFFFFFF)
_AXIS = "x"

ERR_QUEUE_OVERFLOW = 1
ERR_HASH_MISS = 2
ERR_LOGIC = 4


@dataclasses.dataclass
class GHSStats(runtime.EngineStats):
    supersteps: int = 0
    processed: int = 0
    productive: int = 0
    sent_remote: int = 0
    sent_local: int = 0
    halted_fragments: int = 0
    bytes_remote: int = 0
    # per-superstep histories (Fig 3 / Fig 4 analogues)
    queue_history: tuple = ()
    bytes_history: tuple = ()


# ---------------------------------------------------------------------------
# Superstep builder
# ---------------------------------------------------------------------------

def make_superstep(topo: GHSTopology, params: GHSParams, axis_name):
    """Returns superstep(st, do_test, gstep) -> (st, activity, err), traced
    for one shard.  ``do_test`` (traced bool) selects the Test-queue drain;
    ``gstep`` (traced i32) is the global superstep index used for the
    on-device history buffers."""
    S = topo.num_shards
    block = topo.block
    qcap, ocap, xcap = topo.qcap, topo.ocap, topo.xcap
    tsize, lanes = topo.tsize, topo.lanes
    relaxed = bool(params.relaxed_test_queue)
    method = ("hash" if params.use_hashing else "linear")
    if not params.use_hashing and params.hash_table_factor < 0:
        method = "binary"   # sentinel: factor<0 selects binary ablation
    compressed = lanes == 5

    # --- message encode/decode -------------------------------------------
    def encode(mtype, level, state, src, dst, fw, fe):
        u = lambda x: jnp.asarray(x).astype(jnp.uint32)
        if compressed:
            hdr = u(mtype) | (u(state) << 3) | (u(level) << 4)
            return jnp.stack([hdr, u(src), u(dst), u(fw), u(fe)])
        return jnp.stack([u(mtype), u(level), u(state), u(src), u(dst),
                          u(fw), u(fe), jnp.uint32(0)])

    def decode(msg):
        if compressed:
            hdr = msg[0]
            return (hdr & 7, hdr >> 4, (hdr >> 3) & 1,
                    msg[1], msg[2], msg[3], msg[4])
        return (msg[0], msg[1], msg[2], msg[3], msg[4], msg[5], msg[6])

    def msg_type(rows):  # vectorized, for ingest routing
        return (rows[:, 0] & 7) if compressed else rows[:, 0]

    def msg_src_dst(rows):  # vectorized, for the batched edge pre-pass
        return (rows[:, 1], rows[:, 2]) if compressed else (rows[:, 3],
                                                            rows[:, 4])

    def less(w1, e1, w2, e2):
        return (w1 < w2) | ((w1 == w2) & (e1 < e2))

    # --- queue push (masked, branch-free) ---------------------------------
    def push(st: ShardState, msg, dst, my_shard, pred, is_test, pos=None):
        posv = jnp.asarray(POS_UNRESOLVED if pos is None else pos, jnp.int32)
        ds = (dst.astype(jnp.int32) // block)
        local = (ds == my_shard) & pred
        lm = local & ~is_test
        lt = local & is_test
        rm = pred & ~(ds == my_shard)
        # local main queue
        idx = jnp.where(lm, (st.mq_tail % qcap).astype(jnp.int32), qcap)
        mq = st.mq.at[idx].set(msg, mode="drop")
        mq_pos = st.mq_pos.at[idx].set(posv, mode="drop")
        mq_tail = st.mq_tail + lm.astype(jnp.int32)
        # local test queue
        idx = jnp.where(lt, (st.tq_tail % qcap).astype(jnp.int32), qcap)
        tq = st.tq.at[idx].set(msg, mode="drop")
        tq_pos = st.tq_pos.at[idx].set(posv, mode="drop")
        tq_tail = st.tq_tail + lt.astype(jnp.int32)
        # remote ring
        row = jnp.where(rm, ds, S)
        col = jnp.where(rm, (st.og_tail[ds % S] % ocap).astype(jnp.int32),
                        ocap)
        og = st.og.at[row, col].set(msg, mode="drop")
        og_tail = st.og_tail.at[ds % S].add(rm.astype(jnp.int32))
        err = st.err | jnp.where(
            (mq_tail - st.mq_head > qcap) | (tq_tail - st.tq_head > qcap)
            | jnp.any(og_tail - st.og_head > ocap),
            ERR_QUEUE_OVERFLOW, 0).astype(jnp.int32)
        return st._replace(
            mq=mq, mq_pos=mq_pos, mq_tail=mq_tail,
            tq=tq, tq_pos=tq_pos, tq_tail=tq_tail,
            og=og, og_tail=og_tail, err=err,
            n_sent_local=st.n_sent_local + local.astype(jnp.int32),
            n_sent_remote=st.n_sent_remote + rm.astype(jnp.int32),
        )

    def send(st, my_shard, mtype, level, state, src, dst, fw, fe, pred):
        msg = encode(mtype, level, state, src, dst, fw, fe)
        is_test = jnp.asarray(relaxed and mtype == TEST)
        return push(st, msg, dst, my_shard, pred, is_test)

    # --- edge lookup (C2 + ablations) -------------------------------------
    def lookup(st: ShardState, lv, u):
        if method == "hash":
            h0 = hash_slot(lv, u, tsize)

            def cond(c):
                _, done, steps = c
                return (~done) & (steps < tsize)

            def body(c):
                h, _, steps = c
                hit = (st.h_lv[h] == lv) & (st.h_u[h] == u)
                empty = st.h_pos[h] < 0
                return ((h + 1) % tsize, hit | empty, steps + 1)

            h, _, _ = jax.lax.while_loop(cond, body, (h0, jnp.bool_(False),
                                                      jnp.int32(0)))
            h = (h - 1) % tsize
            hit = (st.h_lv[h] == lv) & (st.h_u[h] == u)
            p = jnp.where(hit, st.h_pos[h], -1)
            return p
        a = st.indptr[lv]
        b = st.indptr[lv + 1]
        if method == "linear":
            def cond(c):
                q, found = c
                return (~found) & (q < b)

            def body(c):
                q, _ = c
                return jnp.where(st.nbr[q] == u, q, q + 1), st.nbr[q] == u

            q, found = jax.lax.while_loop(cond, body, (a, jnp.bool_(False)))
            return jnp.where(found, q, -1)
        # binary search over the by-neighbor-id permutation
        def bcond(c):
            lo, hi = c
            return lo < hi

        def bbody(c):
            lo, hi = c
            mid = (lo + hi) // 2
            v = st.nbr[st.byid[mid]]
            return jnp.where(v < u, mid + 1, lo), jnp.where(v < u, hi, mid)

        lo, _ = jax.lax.while_loop(bcond, bbody, (a, b))
        ok = (lo < b) & (st.nbr[st.byid[lo]] == u)
        return jnp.where(ok, st.byid[lo], -1)

    # --- GHS procedures ----------------------------------------------------
    def key_of(st, p):
        return st.ewb[p], st.etb[p]

    def report_proc(st: ShardState, my_shard, lv, pred):
        """GHS (8): if find_count==0 and test_edge==nil, report up in_branch."""
        ib = st.in_branch[lv]
        fire = pred & (st.find_count[lv] == 0) & (st.test_edge[lv] == -1) \
            & (ib >= 0)
        ibq = jnp.maximum(ib, 0)
        st = st._replace(sn=st.sn.at[lv].set(
            jnp.where(fire, FOUND, st.sn[lv])))
        return send(st, my_shard, REPORT, st.ln[lv], 0, block * my_shard + lv,
                    st.nbr[ibq], st.best_w[lv], st.best_e[lv], fire)

    def change_core(st: ShardState, my_shard, lv, pred):
        """GHS (10)."""
        be = st.best_edge[lv]
        valid = pred & (be >= 0)
        beq = jnp.maximum(be, 0)
        on_branch = st.se[beq] == BRANCH
        vme = block * my_shard + lv
        st = send(st, my_shard, CHANGE_CORE, 0, 0, vme, st.nbr[beq], 0, 0,
                  valid & on_branch)
        st = send(st, my_shard, CONNECT, st.ln[lv], 0, vme, st.nbr[beq], 0, 0,
                  valid & ~on_branch)
        se = st.se.at[beq].set(
            jnp.where(valid & ~on_branch, BRANCH, st.se[beq]))
        err = st.err | jnp.where(pred & (be < 0), ERR_LOGIC, 0).astype(
            jnp.int32)
        return st._replace(se=se, err=err)

    def test_proc(st: ShardState, my_shard, lv):
        """GHS (4): probe lightest Basic edge or report."""
        a = st.indptr[lv]
        b = st.indptr[lv + 1]

        def cond(c):
            q, found = c
            return (~found) & (q < b)

        def body(c):
            q, _ = c
            isb = st.se[q] == BASIC
            return jnp.where(isb, q, q + 1), isb

        q, found = jax.lax.while_loop(cond, body, (a, jnp.bool_(False)))
        qq = jnp.minimum(q, b - 1)
        st = st._replace(test_edge=st.test_edge.at[lv].set(
            jnp.where(found, q, -1)))
        st = send(st, my_shard, TEST, st.ln[lv], 0, block * my_shard + lv,
                  st.nbr[qq], st.fnw[lv], st.fne[lv], found)
        return report_proc(st, my_shard, lv, ~found)

    # --- handlers (uniform signature) --------------------------------------
    # args: st, my_shard, u, lv, p, level, state_bit, fw, fe, raw_msg
    def h_connect(st, my_shard, u, lv, p, level, state_bit, fw, fe, raw):
        vme = block * my_shard + lv
        absorb = level < st.ln[lv]
        merge = ~absorb & (st.se[p] != BASIC)
        postpone = ~absorb & (st.se[p] == BASIC)
        se = st.se.at[p].set(jnp.where(absorb, BRANCH, st.se[p]))
        st = st._replace(se=se)
        im_find = st.sn[lv] == FIND
        st = send(st, my_shard, INITIATE, st.ln[lv],
                  jnp.where(im_find, 1, 0), vme, u, st.fnw[lv], st.fne[lv],
                  absorb)
        st = st._replace(find_count=st.find_count.at[lv].add(
            jnp.where(absorb & im_find, 1, 0)))
        kw, ke = key_of(st, p)
        st = send(st, my_shard, INITIATE, st.ln[lv] + 1, 1, vme, u, kw, ke,
                  merge)
        st = push(st, raw, jnp.asarray(vme, jnp.uint32), my_shard, postpone,
                  jnp.bool_(False), pos=p)
        return st, ~postpone

    def h_initiate(st, my_shard, u, lv, p, level, state_bit, fw, fe, raw):
        vme = block * my_shard + lv
        st = st._replace(
            ln=st.ln.at[lv].set(level.astype(jnp.uint32)),
            fnw=st.fnw.at[lv].set(fw), fne=st.fne.at[lv].set(fe),
            sn=st.sn.at[lv].set(jnp.where(state_bit == 1, FIND, FOUND)),
            in_branch=st.in_branch.at[lv].set(p),
            best_edge=st.best_edge.at[lv].set(-1),
            best_w=st.best_w.at[lv].set(INF32),
            best_e=st.best_e.at[lv].set(INF32),
        )
        a = st.indptr[lv]
        b = st.indptr[lv + 1]

        def body(c):
            q, st = c
            fwd = (st.se[q] == BRANCH) & (q != p)
            st = send(st, my_shard, INITIATE, level, state_bit, vme,
                      st.nbr[q], fw, fe, fwd)
            st = st._replace(find_count=st.find_count.at[lv].add(
                jnp.where(fwd & (state_bit == 1), 1, 0)))
            return q + 1, st

        _, st = jax.lax.while_loop(lambda c: c[0] < b, body, (a, st))
        st = jax.lax.cond(state_bit == 1,
                          lambda s: test_proc(s, my_shard, lv),
                          lambda s: s, st)
        return st, jnp.bool_(True)

    def h_test(st, my_shard, u, lv, p, level, state_bit, fw, fe, raw):
        vme = block * my_shard + lv
        postpone = level > st.ln[lv]
        same = (fw == st.fnw[lv]) & (fe == st.fne[lv])
        accept = ~postpone & ~same
        rej = ~postpone & same
        st = send(st, my_shard, ACCEPT, 0, 0, vme, u, 0, 0, accept)
        se = st.se.at[p].set(
            jnp.where(rej & (st.se[p] == BASIC), REJECTED, st.se[p]))
        st = st._replace(se=se)
        was_testing = st.test_edge[lv] == p
        st = send(st, my_shard, REJECT, 0, 0, vme, u, 0, 0,
                  rej & ~was_testing)
        st = jax.lax.cond(rej & was_testing,
                          lambda s: test_proc(s, my_shard, lv),
                          lambda s: s, st)
        st = push(st, raw, jnp.asarray(vme, jnp.uint32), my_shard, postpone,
                  jnp.bool_(relaxed), pos=p)
        return st, ~postpone

    def h_accept(st, my_shard, u, lv, p, level, state_bit, fw, fe, raw):
        st = st._replace(test_edge=st.test_edge.at[lv].set(-1))
        w, e = key_of(st, p)
        better = less(w, e, st.best_w[lv], st.best_e[lv])
        st = st._replace(
            best_edge=st.best_edge.at[lv].set(
                jnp.where(better, p, st.best_edge[lv])),
            best_w=st.best_w.at[lv].set(jnp.where(better, w, st.best_w[lv])),
            best_e=st.best_e.at[lv].set(jnp.where(better, e, st.best_e[lv])),
        )
        st = report_proc(st, my_shard, lv, jnp.bool_(True))
        return st, jnp.bool_(True)

    def h_reject(st, my_shard, u, lv, p, level, state_bit, fw, fe, raw):
        se = st.se.at[p].set(
            jnp.where(st.se[p] == BASIC, REJECTED, st.se[p]))
        st = test_proc(st._replace(se=se), my_shard, lv)
        return st, jnp.bool_(True)

    def h_report(st, my_shard, u, lv, p, level, state_bit, fw, fe, raw):
        vme = block * my_shard + lv
        noncore = p != st.in_branch[lv]
        # non-core: aggregate child report
        st = st._replace(find_count=st.find_count.at[lv].add(
            jnp.where(noncore, -1, 0)))
        better = noncore & less(fw, fe, st.best_w[lv], st.best_e[lv])
        st = st._replace(
            best_edge=st.best_edge.at[lv].set(
                jnp.where(better, p, st.best_edge[lv])),
            best_w=st.best_w.at[lv].set(
                jnp.where(better, fw, st.best_w[lv])),
            best_e=st.best_e.at[lv].set(
                jnp.where(better, fe, st.best_e[lv])),
        )
        st = report_proc(st, my_shard, lv, noncore)
        # core: decide winner side / halt
        postpone = ~noncore & (st.sn[lv] == FIND)
        my_smaller = less(st.best_w[lv], st.best_e[lv], fw, fe)
        st = change_core(st, my_shard, lv, ~noncore & ~postpone & my_smaller)
        halt = (~noncore & ~postpone & ~my_smaller
                & (fw == INF32) & (fe == INF32)
                & (st.best_w[lv] == INF32) & (st.best_e[lv] == INF32))
        st = st._replace(halted=st.halted + halt.astype(jnp.int32))
        st = push(st, raw, jnp.asarray(vme, jnp.uint32), my_shard, postpone,
                  jnp.bool_(False), pos=p)
        return st, ~postpone

    def h_changecore(st, my_shard, u, lv, p, level, state_bit, fw, fe, raw):
        st = change_core(st, my_shard, lv, jnp.bool_(True))
        return st, jnp.bool_(True)

    handlers = [h_connect, h_initiate, h_test, h_accept, h_reject, h_report,
                h_changecore]

    # --- dispatch one message ---------------------------------------------
    def dispatch(st: ShardState, my_shard, raw, pre):
        """``pre`` is the batch-resolved CSR position side-lane value: >= 0
        skips the scalar probe entirely; POS_UNRESOLVED falls back to it."""
        mtype, level, state_bit, src, dst, fw, fe = decode(raw)
        lv = (dst.astype(jnp.int32) - block * my_shard)
        u = src.astype(jnp.int32)
        p = jax.lax.cond(pre >= 0, lambda: pre, lambda: lookup(st, lv, u))
        err = st.err | jnp.where(p < 0, ERR_HASH_MISS, 0).astype(jnp.int32)
        st = st._replace(err=err)
        p = jnp.maximum(p, 0)
        st, productive = jax.lax.switch(
            jnp.clip(mtype.astype(jnp.int32), 0, 6),
            handlers, st, my_shard, u, lv, p, level, state_bit, fw, fe, raw)
        return st._replace(
            n_processed=st.n_processed + 1,
            n_productive=st.n_productive + productive.astype(jnp.int32))

    # --- queue processing ----------------------------------------------------
    def process_main(st: ShardState, my_shard):
        # Budget: the queue snapshot plus slack so freshly-generated local
        # messages (e.g. ChangeCore chains) advance several hops per
        # superstep; bounded so postponed-message spins cannot livelock.
        budget = 2 * (st.mq_tail - st.mq_head) + 64

        def cond(c):
            st, n = c
            return (st.mq_head < st.mq_tail) & (n < budget) & (st.err == 0)

        def body(c):
            st, n = c
            slot = (st.mq_head % qcap).astype(jnp.int32)
            raw = st.mq[slot]
            pre = st.mq_pos[slot]
            st = st._replace(mq_head=st.mq_head + 1)
            return dispatch(st, my_shard, raw, pre), n + 1

        st, _ = jax.lax.while_loop(cond, body, (st, jnp.int32(0)))
        return st

    def process_test_q(st: ShardState, my_shard):
        snapshot = st.tq_tail

        def cond(c):
            st, n = c
            return (st.tq_head < snapshot) & (st.err == 0)

        def body(c):
            st, n = c
            slot = (st.tq_head % qcap).astype(jnp.int32)
            raw = st.tq[slot]
            pre = st.tq_pos[slot]
            st = st._replace(tq_head=st.tq_head + 1)
            return dispatch(st, my_shard, raw, pre), n + 1

        st, _ = jax.lax.while_loop(cond, body, (st, jnp.int32(0)))
        return st

    # --- ingest & flush ------------------------------------------------------
    def ingest(st: ShardState, my_shard):
        flat = st.inbox.reshape(S * xcap, lanes)
        valid = (jnp.arange(xcap)[None, :]
                 < st.in_cnt[:, None]).reshape(-1)
        if method == "hash":
            # Batched pre-pass (C2, vectorized): resolve every incoming
            # message's edge in one lock-step probe sweep over the shard's
            # hash table instead of one scalar probe chain per pop.
            srcs, dsts = msg_src_dst(flat)
            qlv = dsts.astype(jnp.int32) - block * my_shard
            pre = edge_ops.resolve_batch(
                st.h_lv, st.h_u, st.h_pos, qlv, srcs, valid,
                max_probes=min(tsize, 64))
            pre = jnp.where(pre >= 0, pre, jnp.int32(POS_UNRESOLVED))
        else:
            pre = jnp.full(flat.shape[0], POS_UNRESOLVED, jnp.int32)
        istest = jnp.asarray(relaxed) & (msg_type(flat) == TEST)
        to_main = valid & ~istest
        to_test = valid & istest
        pos = st.mq_tail + jnp.cumsum(to_main.astype(jnp.int32)) - 1
        idx = jnp.where(to_main, (pos % qcap).astype(jnp.int32), qcap)
        mq = st.mq.at[idx].set(flat, mode="drop")
        mq_pos = st.mq_pos.at[idx].set(pre, mode="drop")
        mq_tail = st.mq_tail + to_main.sum(dtype=jnp.int32)
        pos = st.tq_tail + jnp.cumsum(to_test.astype(jnp.int32)) - 1
        idx = jnp.where(to_test, (pos % qcap).astype(jnp.int32), qcap)
        tq = st.tq.at[idx].set(flat, mode="drop")
        tq_pos = st.tq_pos.at[idx].set(pre, mode="drop")
        tq_tail = st.tq_tail + to_test.sum(dtype=jnp.int32)
        err = st.err | jnp.where(
            (mq_tail - st.mq_head > qcap) | (tq_tail - st.tq_head > qcap),
            ERR_QUEUE_OVERFLOW, 0).astype(jnp.int32)
        return st._replace(mq=mq, mq_pos=mq_pos, mq_tail=mq_tail,
                           tq=tq, tq_pos=tq_pos, tq_tail=tq_tail,
                           in_cnt=jnp.zeros_like(st.in_cnt), err=err)

    def flush(st: ShardState):
        avail = st.og_tail - st.og_head
        k = jnp.minimum(avail, xcap)
        cols = ((st.og_head[:, None] + jnp.arange(xcap)[None, :]) % ocap
                ).astype(jnp.int32)
        msgs = jnp.take_along_axis(st.og, cols[:, :, None], axis=1)
        mask = jnp.arange(xcap)[None, :] < k[:, None]
        msgs = jnp.where(mask[:, :, None], msgs, 0)
        st = st._replace(og_head=st.og_head + k)
        return st, msgs, k.astype(jnp.int32)

    # --- the superstep -------------------------------------------------------
    def superstep(st: ShardState, do_test, gstep):
        my_shard = (jax.lax.axis_index(axis_name).astype(jnp.int32)
                    if axis_name else jnp.int32(0))
        st = ingest(st, my_shard)
        st = process_main(st, my_shard)
        if relaxed:
            st = jax.lax.cond(do_test,
                              lambda s: process_test_q(s, my_shard),
                              lambda s: s, st)
        st, msgs, k = flush(st)
        if axis_name is not None and S > 1:
            msgs = jax.lax.all_to_all(msgs, axis_name, 0, 0)
            k = jax.lax.all_to_all(k[:, None], axis_name, 0, 0)[:, 0]
            st = st._replace(inbox=msgs, in_cnt=k)
        elif S == 1:
            st = st._replace(inbox=msgs, in_cnt=k)
        activity = ((st.mq_tail - st.mq_head) + (st.tq_tail - st.tq_head)
                    + (st.og_tail - st.og_head).sum()
                    + st.in_cnt.sum().astype(jnp.int32))
        err = st.err
        if axis_name is not None:
            activity = jax.lax.psum(activity, axis_name)
            err = jax.lax.psum(err, axis_name)
        # Per-superstep history, recorded on device (capacity-1 buffers —
        # i.e. history off — simply drop every write past index 0).
        st = st._replace(
            hist_act=st.hist_act.at[gstep].set(activity, mode="drop"),
            hist_sent=st.hist_sent.at[gstep].set(st.n_sent_remote,
                                                 mode="drop"))
        return st, activity, err

    return superstep


# ---------------------------------------------------------------------------
# Compile-cached driver builders (runtime layer, DESIGN.md §6)
# ---------------------------------------------------------------------------

def _state_specs():
    return ShardState(*[P(_AXIS)] * len(ShardState._fields))


def _build_step_fn(topo: GHSTopology, params: GHSParams,
                   mesh: Optional[Mesh]):
    """Legacy per-superstep dispatch: (state, do_test, gstep) ->
    (state, [activity, err]) — ONE fused scalar readback per superstep
    (the old driver's two blocking ``int()`` fetches, stacked).

    Deliberately NOT compile-cached: the seed driver rebuilt and re-jitted
    its superstep on every invocation, and this retained path is the
    before/after baseline for ``bench_superstep_loop.py`` — the runtime
    layer's compile cache is one of the things being measured."""
    step_core = make_superstep(topo, params, _AXIS if mesh is not None
                               else None)
    donate = (0,)
    if mesh is None:
        def f(st, do_test, gstep):
            st, act, err = step_core(st, do_test, gstep)
            return st, jnp.stack([act, err])
        return jax.jit(f, donate_argnums=donate)

    def f(stacked, do_test, gstep):
        st = ShardState(*[a[0] for a in stacked])
        st, act, err = step_core(st, do_test, gstep)
        st = ShardState(*[a[None] for a in st])
        return st, jnp.stack([act, err])

    fn = compat.shard_map(
        f, mesh,
        in_specs=(_state_specs(), P(), P()),
        out_specs=(_state_specs(), P()),
    )
    return jax.jit(fn, donate_argnums=donate)


@functools.lru_cache(maxsize=32)
def _build_interval_fn(topo: GHSTopology, params: GHSParams,
                       mesh: Optional[Mesh]):
    """Device-resident superstep loop: (state, step0, silent0, n_steps) ->
    (state, [abs_steps, silent_streak, err]).

    Runs up to ``n_steps`` supersteps in one ``lax.while_loop`` dispatch,
    breaking early on an error flag or once the consecutive-silent-check
    streak reaches ``empty_iter_cnt_to_break`` (paper §3.6) — the host
    reads back one fused length-3 vector per interval.  The vector carries
    the ABSOLUTE superstep count (``step0 + steps_run``) so the next
    interval can be dispatched straight from the previous one's un-fetched
    device outputs — the hand-off the double-buffered driver needs
    (DESIGN.md §11)."""
    step_core = make_superstep(topo, params, _AXIS if mesh is not None
                               else None)
    check = max(params.check_frequency, 1)
    empty_needed = max(params.empty_iter_cnt_to_break, 1)

    def interval_core(st, step0, silent0, n_steps):
        def cond(c):
            _, i, silent, err = c
            return (i < n_steps) & (silent < empty_needed) & (err == 0)

        def body(c):
            st, i, silent, _ = c
            gstep = step0.astype(jnp.int32) + i
            do_test = (gstep % check) == (check - 1)
            st, act, err = step_core(st, do_test, gstep)
            silent = jnp.where(act == 0, silent + 1, jnp.int32(0))
            return st, i + 1, silent, err

        st, i, silent, err = jax.lax.while_loop(
            cond, body,
            (st, jnp.int32(0), silent0.astype(jnp.int32), jnp.int32(0)))
        return st, jnp.stack([step0.astype(jnp.int32) + i, silent, err])

    donate = (0,)
    if mesh is None:
        return jax.jit(interval_core, donate_argnums=donate)

    def f(stacked, step0, silent0, n_steps):
        st = ShardState(*[a[0] for a in stacked])
        st, scal = interval_core(st, step0, silent0, n_steps)
        return ShardState(*[a[None] for a in st]), scal

    fn = compat.shard_map(
        f, mesh,
        in_specs=(_state_specs(), P(), P(), P()),
        out_specs=(_state_specs(), P()),
    )
    return jax.jit(fn, donate_argnums=donate)


# ---------------------------------------------------------------------------
# Drivers (both route through repro.core.runtime.interval_loop)
# ---------------------------------------------------------------------------

_ERR_DESCRIPTIONS = (
    (ERR_QUEUE_OVERFLOW,
     "ERR_QUEUE_OVERFLOW: a message ring exceeded its capacity — raise "
     "params.queue_capacity (or leave it 0 to auto-size from the shard "
     "adjacency)"),
    (ERR_HASH_MISS,
     "ERR_HASH_MISS: edge hash lookup failed (hash table too small — raise "
     "params.hash_table_factor)"),
    (ERR_LOGIC,
     "ERR_LOGIC: protocol invariant violated (engine bug)"),
)


def _raise_on_err(err: int):
    if err:
        what = "; ".join(d for flag, d in _ERR_DESCRIPTIONS if err & flag)
        raise RuntimeError(
            f"GHS engine error flags: {err:#x} ({what or 'unknown flag'})")


def _device_driver(state, topo, params, mesh, stats, total_cap: int):
    """Fused loop: ≤ 1 host sync per ``check_frequency`` supersteps.

    The superstep / silent-streak counters ride the interval fn's device
    scalar vector (absolute step counts), so the next interval is
    dispatched straight from the previous one's un-fetched outputs — which
    is what lets ``params.interval_pipeline`` double-buffer this driver
    (DESIGN.md §11).  A silent state is a while-loop fixed point (the cond
    fails immediately), so the speculative trailing interval cannot
    perturb the forest; an errored interval's successor wastes bounded
    device work whose results the raise discards."""
    fn = _build_interval_fn(topo, params, mesh)
    interval = max(params.check_frequency, 1)
    empty_needed = max(params.empty_iter_cnt_to_break, 1)
    overlap = (runtime.resolve_interval_pipeline(params.interval_pipeline)
               == 1)
    box = dict(steps=0, dispatched=0)

    def dispatch(s):
        st, scal = s
        # Clamp by the DISPATCHED total: under overlap this runs before
        # the previous interval's readback is consumed.  A clamped-to-zero
        # interval is a device no-op returning its inputs' counters.
        n_steps = max(min(interval, total_cap - box["dispatched"]), 0)
        box["dispatched"] += n_steps
        st, scal = fn(st, scal[0], scal[1], np.int32(n_steps))
        return (st, scal), scal

    def finish(s, vals):
        steps_abs, silent, err = (int(v) for v in np.asarray(vals))
        _raise_on_err(err)
        box["steps"] = steps_abs
        return s, silent >= empty_needed

    state, _ = runtime.interval_loop(
        (state, jnp.zeros((3,), jnp.int32)), dispatch, finish, stats=stats,
        max_intervals=-(-total_cap // interval),
        fail_msg=f"GHS engine did not reach silence in {total_cap} steps",
        overlap=overlap)
    return state, box["steps"]


def _host_driver(state, topo, params, mesh, stats, total_cap: int):
    """Legacy per-superstep loop (``round_loop="host"``), retained as the
    before/after baseline; its two scalar fetches per superstep are fused
    into one stacked transfer."""
    fn = _build_step_fn(topo, params, mesh)
    check = max(params.check_frequency, 1)
    empty_needed = max(params.empty_iter_cnt_to_break, 1)
    box = dict(steps=0, silent=0)

    def dispatch(st):
        step = box["steps"]
        do_test = bool(step % check == check - 1)
        return fn(st, do_test, np.int32(step))

    def finish(st, vals):
        act, err = (int(v) for v in np.asarray(vals))
        _raise_on_err(err)
        box["steps"] += 1
        box["silent"] = box["silent"] + 1 if act == 0 else 0
        return st, box["silent"] >= empty_needed

    state = runtime.interval_loop(
        state, dispatch, finish, stats=stats, max_intervals=total_cap,
        fail_msg=f"GHS engine did not reach silence in {total_cap} steps")
    return state, box["steps"]


def minimum_spanning_forest(
    graph,
    params: GHSParams = DEFAULT_PARAMS,
    mesh: Optional[Mesh] = None,
    max_supersteps: Optional[int] = None,
    collect_history: bool = False,
) -> tuple[ForestResult, GHSStats]:
    """Run the faithful GHS engine; returns forest + execution stats.

    ``graph`` is a host :class:`Graph` or a
    :class:`repro.core.pipeline.DeviceEdges` (mirrored to host once — this
    engine initializes its CSR shards host-side).  ``params.partitioner``
    picks the vertex distribution: non-block partitions are realized as a
    relabeling that preserves edge order and canonical ids
    (:func:`runtime.vertex_partitioned`), so the forest — recorded by
    canonical edge id — is bit-identical for every partitioner.

    ``params.round_loop`` selects the driver: ``"device"`` (default) runs
    ``check_frequency`` supersteps per host dispatch inside a fused
    ``lax.while_loop``; ``"host"`` is the legacy one-superstep-per-dispatch
    loop.  Both produce bit-identical forests.
    """
    graph = runtime.as_graph(graph)
    loop = runtime.resolve_round_loop(params.round_loop)
    S = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    n = graph.num_vertices
    cap = max_supersteps or (40 * n + 2000)
    empty_needed = max(params.empty_iter_cnt_to_break, 1)
    total_cap = cap + empty_needed - 1   # silence-confirmation steps are free
    topo, shards = init_shards(
        runtime.vertex_partitioned(graph, params.partitioner, S), S, params,
        history_capacity=total_cap if collect_history else 1)

    if mesh is not None:
        state = jax.device_put(stack_shards(shards),
                               NamedSharding(mesh, P(_AXIS)))
    else:
        state = jax.tree.map(jnp.asarray, shards[0])

    stats = GHSStats()
    driver = _device_driver if loop == "device" else _host_driver
    state, steps = driver(state, topo, params, mesh, stats, total_cap)
    stats.supersteps = steps

    # Final state fetch: forest + counters + histories, one transfer.
    state_h = jax.device_get(state)
    stats.host_syncs += 1          # final state fetch
    stats.extra_syncs += 1

    # Extract branch edges (union over shards & directions).
    se = np.asarray(state_h.se)
    ceid = np.asarray(state_h.ceid)
    if mesh is None:
        se, ceid = se[None], ceid[None]
    mask = np.zeros(graph.num_edges, dtype=bool)
    for s in range(se.shape[0]):
        sel = se[s] == BRANCH
        mask[ceid[s][sel]] = True
    res = runtime.forest_from_mask(graph, mask)

    bytes_per_msg = topo.lanes * 4
    stats.processed = int(np.sum(np.asarray(state_h.n_processed)))
    stats.productive = int(np.sum(np.asarray(state_h.n_productive)))
    stats.sent_remote = int(np.sum(np.asarray(state_h.n_sent_remote)))
    stats.sent_local = int(np.sum(np.asarray(state_h.n_sent_local)))
    stats.halted_fragments = int(np.sum(np.asarray(state_h.halted)))
    stats.bytes_remote = stats.sent_remote * bytes_per_msg
    if collect_history:
        hist_act = np.asarray(state_h.hist_act)
        hist_sent = np.asarray(state_h.hist_sent)
        if mesh is None:
            hist_act, hist_sent = hist_act[None], hist_sent[None]
        # activity is psum'd (identical on every shard); sends are per-shard
        # cumulative counts, summed here to the global cumulative series.
        stats.queue_history = tuple(
            int(x) for x in hist_act[0][:steps])
        stats.bytes_history = tuple(
            int(x) * bytes_per_msg for x in hist_sent.sum(axis=0)[:steps])
    return res, stats
