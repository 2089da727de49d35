"""Chunked flow streaming: `_slot_step` with the flow axis split into
fixed-size chunks (`JxConfig.flow_chunk`), so populations whose per-flow
working set exceeds one device's memory budget still run.

The slot step factors into three stages:

  1. **Accumulate** (inner `lax.scan` over chunks): per-chunk offered
     rates scatter-add into flat per-link / per-host load accumulators
     via `kernels.link_load.segment_load_chunk`.  Folding chunks
     left-to-right reproduces the monolithic `segment_load` call's
     per-bucket addition chain exactly (both lower to the XLA CPU
     scatter expander, which applies duplicate updates in index = flow
     order), so x64 results are **bit-identical** to the unchunked
     engine — including non-divisible tails, whose pad flows are inert
     (+0.0 contributions onto sums of non-negative rates).
  2. **Link-level mid-slot**: bottleneck fractions, pair-fraction
     tables, queue/utilization integration — O(fabric), no flow axis.
  3. **Emit** (second inner scan over chunks): recompute each chunk's
     offered rate (bit-identical elementwise replay of stage 1 — XLA
     CSEs the duplicate when it keeps both live anyway), gather its
     fabric scale/queue view, and run the per-flow NIC / completion /
     goodput updates, stacking the new per-flow carry as scan outputs.

Both inner scans read only the *old* carry (the monolithic step has no
intra-slot feedback into the per-flow state), so chunk order cannot
create sequencing hazards.  The chunk axis being a `lax.scan` is also
what buys the double-buffered transfer structure: under JAX's async
dispatch XLA overlaps fetching chunk k+1's slice with chunk k's
scatter, without the engine managing buffers by hand.

Not supported here: dense aggregation (chunking exists to avoid its
monolithic gather plans), `TraceSpec` captures (per-slot stacked trace
ys would defeat the memory bound), and the megabatch `lax.switch` route
fallback (lanes give a concrete per-lane route index; evaluating both
route branches per chunk would double the streaming cost).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.jsq_route import pair_fractions as _k_pair_fractions
from repro.kernels.link_load import (bottleneck as _k_bottleneck,
                                     segment_load_chunk)
from repro.kernels.queue_ecn import queue_update as _k_queue_update

from . import engine
from .state import FlowBatch, SimCarry

_EPS = engine._EPS


def _slot_step_chunked(cfg, route, use_war, stack, fbc, assign_c,
                       seg_up, seg_down, seg_acc, seg_up2, seg_down2,
                       seg_dem, seg_vup, seg_vdown, seg_vup2, seg_vdown2,
                       carry, xs):
    t, seg = xs
    up = seg_up[seg] * cfg.uplink_cap
    down = seg_down[seg] * cfg.uplink_cap
    acc = (seg_acc[seg] * cfg.access_cap).T
    up2 = seg_up2[seg] * cfg.core_cap
    down2 = seg_down2[seg] * cfg.core_cap
    if cfg.react:
        upv = seg_vup[seg] * cfg.uplink_cap
        downv = seg_vdown[seg] * cfg.uplink_cap
        up2v = seg_vup2[seg] * cfg.core_cap
        down2v = seg_vdown2[seg] * cfg.core_cap
    else:
        upv, downv, up2v, down2v = up, down, up2, down2
    dem_now = seg_dem[seg]

    nc, ch = fbc.src.shape[:2]
    fdt = fbc.demand.dtype
    tm = jax.tree_util.tree_map
    P, L, H = cfg.n_planes, cfg.n_leaves, cfg.n_hosts
    S, A = cfg.n_spines, cfg.n_aggs
    J, cpa = cfg.n_paths, cfg.cores_per_agg
    pods, lpp = cfg.n_pods, cfg.leaves_per_pod
    fat = cfg.kind == "fat_tree"
    pair_route = route == engine.ROUTE_PAIR
    pk = jnp.arange(P)[None, :]

    def chunk_view(a):
        return jnp.reshape(a, (nc, ch) + tuple(a.shape[1:]))

    xs_chunks = (fbc, tm(chunk_view, carry.nic), chunk_view(carry.done),
                 assign_c)

    def offered_of(fb_c, nic_k, done_k):
        """One chunk's plane-split offered rate — evaluated identically
        by both inner scans (all inputs come from the old carry)."""
        demand = jnp.where(done_k | (t < fb_c.start_slot), 0.0,
                           fb_c.demand)
        if cfg.n_phases:
            demand = demand * dem_now[fb_c.phase]
        offered = engine._plane_split(cfg, nic_k, demand, stack)
        return offered, jnp.where(fb_c.same_leaf[:, None], 0.0, offered)

    # ---- pass 1: stream chunks through the scatter-add accumulators --
    if pair_route:
        accs0 = {"pair": jnp.zeros(P * L * L, fdt)}
    elif not fat:
        accs0 = {"up": jnp.zeros(P * L * S, fdt),
                 "dn": jnp.zeros(P * S * L, fdt)}
    else:
        accs0 = {"Au": jnp.zeros(P * L * A, fdt),
                 "Ad": jnp.zeros(P * A * L, fdt),
                 "Bu": jnp.zeros(P * pods * J, fdt),
                 "Bd": jnp.zeros(P * pods * J, fdt)}
    accs0["tx"] = jnp.zeros(H * P, fdt)
    accs0["rx"] = jnp.zeros(H * P, fdt)

    def accumulate(accs, xs_k):
        fb_c, nic_k, done_k, asg_k = xs_k[:4]
        offered, fr = offered_of(fb_c, nic_k, done_k)
        accs = dict(accs)
        accs["tx"] = segment_load_chunk(
            accs["tx"], offered, fb_c.src[:, None] * P + pk)
        accs["rx"] = segment_load_chunk(
            accs["rx"], offered, fb_c.dst[:, None] * P + pk)
        if pair_route:
            pair_idx = fb_c.src_leaf * L + fb_c.dst_leaf
            accs["pair"] = segment_load_chunk(
                accs["pair"], fr, pk * (L * L) + pair_idx[:, None])
        elif not fat:
            assign = asg_k[seg]
            k_up = pk * (L * S) + fb_c.src_leaf[:, None] * S + assign
            k_dn = pk * (S * L) + assign * L + fb_c.dst_leaf[:, None]
            accs["up"] = segment_load_chunk(accs["up"], fr, k_up)
            accs["dn"] = segment_load_chunk(accs["dn"], fr, k_dn)
        else:
            assign = asg_k[seg]
            a_of = assign // cpa
            pod_s = fb_c.src_leaf // lpp
            pod_d = fb_c.dst_leaf // lpp
            # intra-pod flows add exact 0.0 to the stage-B buckets —
            # same contract as the monolithic sparse path
            vB = jnp.where((pod_s != pod_d)[:, None], fr, 0.0)
            kAu = pk * (L * A) + fb_c.src_leaf[:, None] * A + a_of
            kAd = pk * (A * L) + a_of * L + fb_c.dst_leaf[:, None]
            kBu = pk * (pods * J) + pod_s[:, None] * J + assign
            kBd = pk * (pods * J) + pod_d[:, None] * J + assign
            accs["Au"] = segment_load_chunk(accs["Au"], fr, kAu)
            accs["Ad"] = segment_load_chunk(accs["Ad"], fr, kAd)
            accs["Bu"] = segment_load_chunk(accs["Bu"], vB, kBu)
            accs["Bd"] = segment_load_chunk(accs["Bd"], vB, kBd)
        return accs, None

    accs, _ = jax.lax.scan(accumulate, accs0, xs_chunks)

    # ---- mid-slot: link-level math, transcribed from the monolithic
    # route branches (`_route_pair[_ft]` / `_route_ecmp[_ft]`) ----
    bh_mid = None
    if pair_route and not fat:
        rate_pair = accs["pair"].reshape(P, L, L)
        rw_arr = downv / jnp.maximum(
            downv.max(axis=1, keepdims=True), 1e-9)
        if isinstance(use_war, bool):
            rw = rw_arr if use_war else None
        else:
            rw = jnp.where(use_war, rw_arr, jnp.ones_like(downv))
        pair = engine._pair_fractions(cfg, carry.q_up, carry.q_down,
                                      upv, downv, rw)
        load_up = jnp.einsum("plm,plms->pls", rate_pair, pair)
        load_down = jnp.einsum("plm,plms->psm", rate_pair, pair)
        f_up, f_down = engine._bottleneck(cfg, up, down, load_up,
                                          load_down)
        scale_pair = jnp.minimum(
            f_up[:, :, None, :],
            f_down.transpose(0, 2, 1)[:, None, :, :])
        path_scale = (pair * scale_pair).sum(-1).reshape(P, L * L)
        q_pair = (carry.q_up[:, :, None, :] +
                  carry.q_down.transpose(0, 2, 1)[:, None, :, :])
        q_tab = (pair * q_pair).sum(-1).reshape(P, L * L)
        if cfg.react:
            cap = jnp.minimum(up[:, :, None, :],
                              jnp.swapaxes(down, 1, 2)[:, None, :, :])
            bh_mid = (rate_pair[..., None] * pair * (cap <= _EPS)).sum()
    elif pair_route:
        rate_pair = accs["pair"].reshape(P, L, L)
        aj, pol = engine._ft_maps(cfg)
        cross_t = (pol[:, None] != pol[None, :])[None, :, :, None]
        upJ = upv[:, :, aj]
        dnJ = downv[:, aj, :]
        capA = jnp.minimum(upJ[:, :, None, :],
                           dnJ.transpose(0, 2, 1)[:, None, :, :])
        up2L = up2v[:, pol, :]
        dn2L = down2v[:, pol, :]
        capB = jnp.minimum(up2L[:, :, None, :], dn2L[:, None, :, :])
        cap = jnp.where(cross_t, jnp.minimum(capA, capB), capA)
        qA = (carry.q_up[:, :, aj][:, :, None, :] +
              carry.q_down[:, aj, :].transpose(0, 2, 1)[:, None, :, :])
        qB = (carry.q2_up[:, pol, :][:, :, None, :] +
              carry.q2_down[:, pol, :][:, None, :, :])
        q = qA + jnp.where(cross_t, qB, 0.0)
        eff = jnp.minimum(dnJ, dn2L.transpose(0, 2, 1))
        rw_arr = eff / jnp.maximum(eff.max(axis=1, keepdims=True), 1e-9)
        if isinstance(use_war, bool):
            rw = rw_arr if use_war else None
        else:
            rw = jnp.where(use_war, rw_arr, jnp.ones_like(rw_arr))
        w = cap if rw is None \
            else cap * rw.transpose(0, 2, 1)[:, None, :, :]
        pair = _k_pair_fractions(q, cap, w, nbins=cfg.jsq_bins,
                                 temperature=cfg.ar_temperature,
                                 qmax=8.0, use_pallas=cfg.use_pallas)
        loadJ_up = jnp.einsum("plm,plmj->plj", rate_pair, pair)
        loadJ_dn = jnp.einsum("plm,plmj->pmj", rate_pair, pair)
        load_up = loadJ_up.reshape(P, L, A, cpa).sum(-1)
        load_down = loadJ_dn.reshape(P, L, A, cpa).sum(-1) \
            .transpose(0, 2, 1)
        ratex = rate_pair * (pol[:, None] != pol[None, :])[None]
        loadB_up = jnp.einsum("plm,plmj->plj", ratex, pair) \
            .reshape(P, pods, lpp, J).sum(2)
        loadB_dn = jnp.einsum("plm,plmj->pmj", ratex, pair) \
            .reshape(P, pods, lpp, J).sum(2)
        fA_up, fA_dn = engine._bottleneck(cfg, up, down, load_up,
                                          load_down)
        fB_up, fB_dn = engine._bottleneck(cfg, up2, down2, loadB_up,
                                          loadB_dn)
        sA = jnp.minimum(
            fA_up[:, :, aj][:, :, None, :],
            fA_dn[:, aj, :].transpose(0, 2, 1)[:, None, :, :])
        sB = jnp.minimum(fB_up[:, pol, :][:, :, None, :],
                         fB_dn[:, pol, :][:, None, :, :])
        scale_pair = jnp.where(cross_t, jnp.minimum(sA, sB), sA)
        path_scale = (pair * scale_pair).sum(-1).reshape(P, L * L)
        q_tab = (pair * q).sum(-1).reshape(P, L * L)
        if cfg.react:
            capA_p = jnp.minimum(
                up[:, :, aj][:, :, None, :],
                down[:, aj, :].transpose(0, 2, 1)[:, None, :, :])
            capB_p = jnp.minimum(up2[:, pol, :][:, :, None, :],
                                 down2[:, pol, :][:, None, :, :])
            cap_p = jnp.where(cross_t, jnp.minimum(capA_p, capB_p),
                              capA_p)
            bh_mid = (rate_pair[..., None] * pair
                      * (cap_p <= _EPS)).sum()
    elif not fat:
        load_up = accs["up"].reshape(P, L, S)
        load_down = accs["dn"].reshape(P, S, L)
        f_up, f_down = engine._bottleneck(cfg, up, down, load_up,
                                          load_down)
    else:
        load_up = accs["Au"].reshape(P, L, A)
        load_down = accs["Ad"].reshape(P, A, L)
        loadB_up = accs["Bu"].reshape(P, pods, J)
        loadB_dn = accs["Bd"].reshape(P, pods, J)
        fA_up, fA_dn = engine._bottleneck(cfg, up, down, load_up,
                                          load_down)
        fB_up, fB_dn = engine._bottleneck(cfg, up2, down2, loadB_up,
                                          loadB_dn)

    load_acc_tx = accs["tx"].reshape(H, P)
    load_acc_rx = accs["rx"].reshape(H, P)
    f_acc_tx = _k_bottleneck(acc, load_acc_tx, eps=_EPS,
                             use_pallas=cfg.use_pallas)
    f_acc_rx = _k_bottleneck(acc, load_acc_rx, eps=_EPS,
                             use_pallas=cfg.use_pallas)

    # queue evolution reads the OLD carry + the accumulated loads, so it
    # can run before the per-flow pass (the monolithic step has no
    # intra-slot queue feedback either)
    q_up, util = _k_queue_update(carry.q_up, load_up, up,
                                 q_cap=cfg.q_cap, eps=_EPS,
                                 use_pallas=cfg.use_pallas)
    q_down, _ = _k_queue_update(carry.q_down, load_down, down,
                                q_cap=cfg.q_cap, eps=_EPS,
                                use_pallas=cfg.use_pallas)
    if fat:
        q2_up, _ = _k_queue_update(carry.q2_up, loadB_up, up2,
                                   q_cap=cfg.q_cap, eps=_EPS,
                                   use_pallas=cfg.use_pallas)
        q2_down, _ = _k_queue_update(carry.q2_down, loadB_dn, down2,
                                     q_cap=cfg.q_cap, eps=_EPS,
                                     use_pallas=cfg.use_pallas)
    else:
        q2_up, q2_down = carry.q2_up, carry.q2_down

    # ---- pass 2: stream chunks through the per-flow fabric gathers.
    # Only the gather-heavy delivery math stays inside the chunk scan;
    # the NIC / completion / goodput updates run once on the flat
    # (F, ...) results below, in the monolithic step's exact op
    # order — keeping those mul-add chains out of the scan body, where
    # XLA's small-shape codegen (scalar FMA contraction at chunk sizes
    # like 1) would cost the last ulp of x64 parity. ----
    p_io = jnp.arange(P)[None, :].repeat(ch, 0)

    def emit(_, xs_k):
        fb_c, nic_k, done_k, asg_k = xs_k
        offered, fr = offered_of(fb_c, nic_k, done_k)
        emit_bh = ()
        if pair_route:
            pair_idx = fb_c.src_leaf * L + fb_c.dst_leaf
            through = fr * path_scale[:, pair_idx].T
            qmean = q_tab[:, pair_idx].T
        elif not fat:
            assign = asg_k[seg]
            scale_f = jnp.minimum(
                f_up[p_io, fb_c.src_leaf[:, None], assign],
                f_down[p_io, assign, fb_c.dst_leaf[:, None]])
            through = fr * scale_f
            qmean = (carry.q_up[p_io, fb_c.src_leaf[:, None], assign] +
                     carry.q_down[p_io, assign, fb_c.dst_leaf[:, None]])
            if cfg.react:
                capF = jnp.minimum(
                    up[p_io, fb_c.src_leaf[:, None], assign],
                    down[p_io, assign, fb_c.dst_leaf[:, None]])
                emit_bh = (fr * (capF <= _EPS),)
        else:
            assign = asg_k[seg]
            a_of = assign // cpa
            pod_s = fb_c.src_leaf // lpp
            pod_d = fb_c.dst_leaf // lpp
            cross = (pod_s != pod_d)[:, None]
            sAf = jnp.minimum(
                fA_up[p_io, fb_c.src_leaf[:, None], a_of],
                fA_dn[p_io, a_of, fb_c.dst_leaf[:, None]])
            sBf = jnp.minimum(fB_up[p_io, pod_s[:, None], assign],
                              fB_dn[p_io, pod_d[:, None], assign])
            through = fr * jnp.where(cross, jnp.minimum(sAf, sBf), sAf)
            qAf = (carry.q_up[p_io, fb_c.src_leaf[:, None], a_of] +
                   carry.q_down[p_io, a_of, fb_c.dst_leaf[:, None]])
            qBf = (carry.q2_up[p_io, pod_s[:, None], assign] +
                   carry.q2_down[p_io, pod_d[:, None], assign])
            qmean = qAf + jnp.where(cross, qBf, 0.0)
            if cfg.react:
                capAf = jnp.minimum(
                    up[p_io, fb_c.src_leaf[:, None], a_of],
                    down[p_io, a_of, fb_c.dst_leaf[:, None]])
                capBf = jnp.minimum(
                    up2[p_io, pod_s[:, None], assign],
                    down2[p_io, pod_d[:, None], assign])
                capF = jnp.where(cross, jnp.minimum(capAf, capBf),
                                 capAf)
                emit_bh = (fr * (capF <= _EPS),)
        up_alive_tx = acc[fb_c.src] > _EPS
        up_alive_rx = acc[fb_c.dst] > _EPS
        local = jnp.where(fb_c.same_leaf[:, None], offered, 0.0)
        acc_scale = jnp.minimum(f_acc_tx[fb_c.src], f_acc_rx[fb_c.dst])
        achieved_pp = (through + local) * acc_scale
        achieved_pp = jnp.where(up_alive_tx & up_alive_rx, achieved_pp,
                                0.0)
        qmean = jnp.where(fb_c.same_leaf[:, None], 0.0, qmean)
        probe_ok = (acc[fb_c.src] > _EPS) & (acc[fb_c.dst] > _EPS)
        stalled = ((offered > 1e-9) & (achieved_pp <= 1e-9)).any(1)
        achieved = jnp.where(stalled, 0.0, achieved_pp.sum(1))
        w = jnp.maximum(offered, _EPS)
        return None, (achieved, w, qmean, probe_ok) + emit_bh

    _, ys2 = jax.lax.scan(emit, None, xs_chunks)

    def flat(a):
        return jnp.reshape(a, (nc * ch,) + tuple(a.shape[2:]))

    achieved = flat(ys2[0])
    w = flat(ys2[1])
    qmean = flat(ys2[2])
    probe_ok = flat(ys2[3])

    # ---- per-flow control/accounting updates, verbatim monolithic ----
    nic_new, rtt, ecn = engine._nic_update(cfg, carry.nic, qmean,
                                           probe_ok, t, stack)
    remaining = carry.remaining - achieved
    newly = (~carry.done) & (remaining <= 0)
    qdelay = (((rtt * w).sum(1) / w.sum(1)) - cfg.base_rtt_us) \
        / cfg.slot_us
    completion = jnp.where(
        newly, t + jnp.ceil(qdelay).astype(carry.completion.dtype),
        carry.completion)
    done = carry.done | newly
    r = cfg.record_every
    n_rec = (cfg.slots + r - 1) // r
    w0 = int(n_rec * cfg.warmup_frac)
    rec = (t % r) == 0
    counted = rec & ((t // r) >= w0) if n_rec > w0 else rec
    goodput_sum = carry.goodput_sum + jnp.where(counted, achieved, 0.0)

    new_carry = SimCarry(
        q_up=q_up, q_down=q_down, q2_up=q2_up, q2_down=q2_down,
        nic=nic_new, remaining=remaining, done=done,
        completion=completion, goodput_sum=goodput_sum, util_up=util)
    total = achieved.sum()
    if not cfg.react:
        return new_carry, total
    bh = bh_mid if pair_route else flat(ys2[4]).sum()
    return new_carry, (total, bh)


def simulate_chunked(cfg, stack: engine.StackIdx, carry0: SimCarry,
                     fb: FlowBatch, seg_up, seg_down, seg_acc, seg_up2,
                     seg_down2, seg_dem, seg_vup, seg_vdown, seg_vup2,
                     seg_vdown2, assign_segments, seg_id):
    """`engine._simulate`'s streaming twin (`cfg.flow_chunk > 0`).
    Same operands (minus the gather plans), same return contract (minus
    trace tails); dispatched from inside `_simulate`, so every megabatch
    lane streams transparently."""
    ch = int(cfg.flow_chunk)
    if cfg.agg_mode != "sparse":
        raise ValueError(
            "flow_chunk requires agg_mode='sparse' (the dense gather "
            "plans are exactly the monolithic layout chunking avoids)")
    if cfg.trace.enabled:
        raise NotImplementedError(
            "flow_chunk does not compose with TraceSpec captures")
    if not isinstance(stack.route, int):
        raise NotImplementedError(
            "chunked streaming needs a concrete per-lane route index "
            "(megabatch lane-sorts elements); the per-element "
            "lax.switch fallback is unsupported")
    route, use_war = stack.route, stack.is_war
    F = int(fb.src.shape[0])
    if F % ch:
        raise ValueError(
            f"{F} flows are not a multiple of the chunk length {ch} "
            "(megabatch rounds its flow bucket up to one)")
    nc = F // ch
    assign = jnp.asarray(assign_segments)
    # chunk-major views, built once outside the scan: flow columns as
    # (nc, ch, ...), the assignment segments as (nc, n_seg, ch, P)
    fbc = FlowBatch(*[jnp.reshape(jnp.asarray(a),
                                  (nc, ch) + tuple(a.shape[1:]))
                      for a in fb])
    assign_c = jnp.moveaxis(
        assign.reshape(assign.shape[0], nc, ch, assign.shape[2]), 1, 0)
    step = partial(_slot_step_chunked, cfg, route, use_war, stack, fbc,
                   assign_c,
                   jnp.asarray(seg_up), jnp.asarray(seg_down),
                   jnp.asarray(seg_acc), jnp.asarray(seg_up2),
                   jnp.asarray(seg_down2), jnp.asarray(seg_dem),
                   jnp.asarray(seg_vup), jnp.asarray(seg_vdown),
                   jnp.asarray(seg_vup2), jnp.asarray(seg_vdown2))
    xs = (jnp.arange(cfg.slots), seg_id)
    carry, ys = jax.lax.scan(step, carry0, xs)
    bh = ()
    if cfg.react:
        totals, bh = ys[0], (ys[1],)
    else:
        totals = ys
    r = cfg.record_every
    n_rec = (cfg.slots + r - 1) // r
    w0 = int(n_rec * cfg.warmup_frac)
    frames = (n_rec - w0) if n_rec > w0 else n_rec
    return (carry.goodput_sum / frames, carry.completion,
            totals, carry.util_up) + bh
