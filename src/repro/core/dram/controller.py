"""Unified memory-controller layer (layer 2 of 3).

One `lax.scan` step = one served memory request, for any number of cores
sharing one channel. The controller owns everything the bank/subarray timing
machine (:mod:`engine`) does not:

* **per-core visibility** — when each core's head request becomes visible to
  the controller: compute-gap pacing, dependent-load serialization, and the
  ROB/MSHR-bounded request window (request ``i`` waits for request
  ``i - mlp_window``'s completion);
* **completion rings** — one ``_RING``-deep ring of completion cycles per
  core, read back by the visibility rules above (``validate_mlp_window``
  guards the ``mlp_window < _RING`` invariant at every entry point);
* **request scheduling** — every step the pluggable scheduler
  (:mod:`schedulers`) keys the cores' live head requests and the controller
  serves ``argmin``;
* **refresh bookkeeping** — per-bank staggered tREFI deadlines under the
  refresh-policy ladder (:mod:`repro.core.dram.refresh`, docs/refresh.md):
  a due bank delays the visibility of the requests its burst blocks (all of
  them under blocking REFab/REFpb, only the refreshed subarray's under
  SARP — and under DSARP+MASA), DARP additionally schedules the bursts
  themselves (idle pull-in, bounded postpone, write-shadow
  parallelization), and every mode directs the timing layer to close the
  refreshed row(s).

``engine.simulate*`` instantiates this scan with one core and
``commands.simulate_mix_commands`` with C cores;
``multicore.simulate_multicore*`` run their mixes on the lanes of one C-core
scan (:func:`_simulate_mixes_lanes`). Every executor calls the same step
math (``engine._step_math``), scheduler keys (``schedulers.request_key``)
and refresh closures (:func:`_refresh_fns`) — there is exactly one
implementation of the shared-channel semantics.

Scan carry (see :mod:`repro.core.dram.state_layout`): the engine's four
packed buffers plus a ``[C, CORE_F]`` per-core vector, the ``[C, _RING]``
completion rings, and (when refreshing) a ``[nb, REF_F]`` refresh table —
six int32 buffers total, updated with single-row dynamic scatters. The
scan's ``unroll`` factor is tunable (``_SCAN_UNROLL``, 1: a CPU choice);
input-buffer donation was evaluated and removed — the scan already updates
its carry in place and the only outputs are a handful of scalars, so XLA
finds no donated buffer to reuse (it warns instead). docs/performance.md
records both measurements.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dram import engine as _engine
from repro.core.dram import state_layout as L
from repro.core.dram.policies import Policy
from repro.core.dram.schedulers import request_key
from repro.core.dram.timing import DramTiming

_RING = _engine._RING
_NEG = _engine._NEG

#: Partial-unroll factor for the per-trace controller scan
#: (:func:`_simulate_controller`; results are bit-identical for any value).
#: It serves only ``simulate()``, command export and closed-row batches, none
#: of which a benchmark cell runs, so it is a CPU choice: **no unroll**. The
#: step is almost entirely sequential gather/scatter, so unrolling multiplies
#: code size without exposing parallelism.
_SCAN_UNROLL = 1

#: Partial-unroll factor for the lane-batched scans
#: (:func:`_simulate_stacked_lanes` and the lane-major mix scan
#: :func:`_simulate_mixes_lanes`; bit-identical for any value). The lane
#: step carries O(B) vector work per sequential dependency, so a 2-way
#: unroll overlaps one step's scatter with the next step's gather math.
#: Measured on a TPU v5e: a step of four 4-core mixes costs 11.76 us at 2
#: against 12.19 us at 1 (PERF.md, the lane-major mix scan entry).
_LANES_UNROLL = 2


def validate_mlp_window(mlp_window) -> None:
    """Enforce the completion-ring invariant ``mlp_window < _RING``.

    The ROB-limit rule reads the ring ``mlp_window`` entries back; a window
    as large as the ring would read the slot the current request is about to
    overwrite — silently corrupting completions (e.g. a ``CoreModel`` with
    ``mshr >= 64``). Checked host-side at every ``simulate*`` entry.
    """
    mw = np.asarray(mlp_window)
    if (mw >= _RING).any() or (mw < 1).any():
        raise ValueError(
            f"mlp_window must be in [1, {_RING - 1}] (completion ring holds "
            f"{_RING} entries and request i waits on request i - mlp_window); "
            f"got {np.unique(mw).tolist()}. Reduce CoreModel.mshr or enlarge "
            f"engine._RING.")


def _refresh_due0(nb: int, t_refi: int) -> jax.Array:
    # stagger per-bank refresh deadlines (real controllers do) to avoid bursts
    return (jnp.arange(nb, dtype=jnp.int32) * max(t_refi // max(nb, 1), 1)
            + t_refi)


def _refresh_table0(n_banks: int, t: DramTiming, refresh_mode: int):
    """Initial per-bank refresh table [nb, REF_F] (None when refresh is off).

    The staggered tREFI deadline plus the in-flight refresh burst (end
    cycle, refreshed subarray). Once a served request triggers a refresh and
    the deadline advances, later heads to that bank must still see the burst
    until it ends — other cores' heads (C > 1), and, under DSARP+MASA, even
    the same core's: a non-target-subarray request is not blocked, so
    vis_prev does not advance past ref_end and a later target-subarray
    request would otherwise read the subarray mid-burst. Under blocking
    refresh (mode 1) the single-core vis_prev chain does carry every later
    request past ref_end, so there this state never binds.
    """
    if not refresh_mode:
        return None
    return (jnp.zeros((n_banks, L.REF_F), jnp.int32)
            .at[:, L.REF_NEXT_DUE].set(_refresh_due0(n_banks, t.t_refi)))


def _refresh_fns(policy: int, t: DramTiming, n_subarrays: int,
                 refresh_mode: int, emit_commands: bool):
    """Build the three refresh closures shared by every executor.

    Returned as ``(head_visibility, update_ref, ref_cmds)``; the scan paths
    in :func:`_simulate_controller` and :func:`_simulate_stacked_lanes` and
    the Pallas kernel bodies (:mod:`repro.core.dram.pallas_step`) call the
    SAME functions, so the refresh semantics cannot diverge between
    executors. The closures are elementwise math on refresh rows the caller
    has already gathered; reading the table and writing it back is the
    caller's contract, as the block movement around ``engine._step_math``
    is.
    """
    is_masa = policy == Policy.MASA
    zero = jnp.int32(0)

    def head_visibility(refb, vis, hs, hwr):
        """Refresh gating of one step's head visibility (shared C=1 / C>1).

        ``refb`` is the head bank's refresh row, fields first (``[REF_F]``,
        or ``[REF_F, C]`` / ``[REF_F, B]`` for vectors of heads or lanes);
        ``vis/hs/hwr`` are matching [C] / [B] vectors (or scalars for the
        C=1 fast path). Returns the gated ``vis`` plus the refresh directive
        for the heads. ``refresh_mode`` dispatch is static (Python branches):

        * modes 1/2 (REFab / DSARP) — the historical deadline machinery,
          kept literally unchanged (regression-pinned bit-for-bit);
        * modes 3/5 (REFpb / SARP) — same machinery with the per-bank
          ``tRFCpb`` burst; SARP blocks only the refreshed subarray's
          requests, with or without MASA (refresh uses no global bitlines);
        * mode 4 (DARP) — refreshes are scheduled, not fired: pulled into
          the bank's idle gap before this request, postponed under demand
          pressure (signed debt bounded by ``ref_postpone_max`` both ways),
          parallelized with writes (the write-shadow refresh is committed in
          ``update_ref``, where the write's completion cycle is known); only
          debt overflowing the window forces a blocking burst.
        """
        busy_end = refb[L.REF_BUSY_UNTIL]
        if refresh_mode in (1, 2):
            # a burst already started by an earlier step still blocks the bank
            busy_blocks = (vis < busy_end) & (
                jnp.bool_(refresh_mode == 1) | jnp.bool_(not is_masa)
                | (hs == refb[L.REF_BUSY_TARGET]))
            vis = jnp.where(busy_blocks, busy_end, vis)
            due = refb[L.REF_NEXT_DUE]
            ref_pending = vis >= due
            ref_end = due + t.t_rfc
            ref_target = (due // t.t_refi) % n_subarrays
            blocks = ref_pending & (jnp.bool_(refresh_mode == 1)
                                    | jnp.bool_(not is_masa)
                                    | (hs == ref_target))
            vis = jnp.where(blocks, jnp.maximum(vis, ref_end), vis)
            return vis, dict(pending=ref_pending, end=ref_end,
                             target=ref_target, due=due)

        if refresh_mode in (3, 5):
            # REFpb / SARP: deadline-fired tRFCpb bursts. SARP gates only
            # same-subarray requests — subarray-level refresh parallelism
            # without MASA's designation hardware.
            sarp = refresh_mode == 5
            busy_blocks = vis < busy_end
            if sarp:
                busy_blocks &= hs == refb[L.REF_BUSY_TARGET]
            vis = jnp.where(busy_blocks, busy_end, vis)
            due = refb[L.REF_NEXT_DUE]
            ref_pending = vis >= due
            ref_end = due + t.t_rfc_pb
            ref_target = (due // t.t_refi) % n_subarrays
            blocks = ref_pending & ((hs == ref_target) if sarp
                                    else jnp.bool_(True))
            vis = jnp.where(blocks, jnp.maximum(vis, ref_end), vis)
            return vis, dict(pending=ref_pending, end=ref_end,
                             target=ref_target, due=due)

        # mode 4: DARP — dynamic access-refresh parallelization over REFpb.
        # A matured deadline does NOT stall the bank: the obligation is
        # postponed (debt) and drained out of the demand stream's way —
        # eagerly during idle gaps and in write shadows — until the debt
        # overflows the spec window and forces blocking bursts. The eager
        # drain is deliberately not an oracle: bursts start back-to-back at
        # the gap's start without knowing when the next request arrives, so
        # a straddling burst makes the arrival wait for its remainder.
        pmax = jnp.int32(t.ref_postpone_max)
        vis = jnp.where(vis < busy_end, busy_end, vis)   # in-flight burst
        due, debt = refb[L.REF_NEXT_DUE], refb[L.REF_DEBT]
        # every tREFI deadline crossed by this request's arrival adds one
        # owed refresh; the deadline ladder advances past vis in one step
        crossings = jnp.where(vis >= due, (vis - due) // t.t_refi + 1, 0)
        owed = debt + crossings
        new_due = due + crossings * t.t_refi
        # idle drain: HPCA'14's idle predictor (Sec. 4.2) waits until the
        # bank's queue has been empty for a while before launching a
        # pull-in. Modeled as one burst-length of patience: bursts start
        # back-to-back at gap_start + tRFCpb, so short gaps never launch
        # (no collision), long gaps absorb refreshes for free, and a
        # medium gap's straddling burst makes this arrival wait for its
        # remainder — the predictor is not an oracle.
        gap_start = jnp.maximum(refb[L.REF_LAST_END], busy_end)
        launch = gap_start + t.t_rfc_pb          # patience window
        avail = jnp.maximum(vis - launch, 0)     # idle observed past it
        n_idle = jnp.minimum(owed,
                             (avail + t.t_rfc_pb - 1) // t.t_rfc_pb)
        drain_end = launch + n_idle * t.t_rfc_pb
        vis = jnp.where(n_idle > 0, jnp.maximum(vis, drain_end), vis)
        owed = owed - n_idle
        # postpone: demand requests go first while the debt fits the spec
        # window; the overflow forces blocking bursts in front of this one
        n_forced = jnp.maximum(owed - pmax, 0)
        forced_at = vis                          # forced chain start cycle
        vis = vis + n_forced * t.t_rfc_pb
        owed = owed - n_forced
        chain_end = jnp.where(n_forced > 0, vis, drain_end)
        # write-refresh parallelization: the core never stalls on a write's
        # completion, so an owed refresh rides the write burst's shadow
        # (committed in update_ref, where the write's completion is known).
        # Gated on the idle drain falling behind (debt >= 2) — HPCA'14's WRP
        # refreshes during write *drains*, i.e. when demand pressure has
        # already kept the banks from refreshing in idle time.
        shadow = hwr & (owed >= 2)
        pending = (n_idle > 0) | (n_forced > 0) | shadow
        d = dict(pending=pending, due=new_due,
                 debt=owed - shadow.astype(jnp.int32),
                 act=((n_idle > 0) | (n_forced > 0)),
                 end=chain_end, shadow=shadow)
        if emit_commands:
            # burst-chain geometry for the command log: extra int lanes ride
            # the directive (they survive the C-core gather; update_ref
            # ignores them). The shadow burst's start is the write's
            # completion — known only after the timing step (ref_cmds).
            d.update(n_idle=n_idle, launch=launch,
                     n_forced=n_forced, forced_at=forced_at)
        return vis, d

    def update_ref(old_row, directive, vis, comp):
        """The served bank's refresh row after this step.

        ``old_row`` is the row before it, fields last (``[REF_F]``, or
        ``[B, REF_F]`` with ``directive``/``vis``/``comp`` as [B] lane
        vectors); returns the new row in the same shape.
        """
        if refresh_mode == 4:
            # DARP rows advance unconditionally: the deadline ladder and the
            # debt carry even when no refresh was performed this step.
            shadow_end = jnp.where(directive["shadow"], comp + t.t_rfc_pb, 0)
            busy = jnp.maximum(old_row[..., L.REF_BUSY_UNTIL],
                               jnp.maximum(
                                   jnp.where(directive["act"],
                                             directive["end"], 0),
                                   shadow_end))
            return jnp.stack([
                directive["due"], busy,
                jnp.broadcast_to(zero, jnp.shape(busy)), directive["debt"],
                jnp.maximum(old_row[..., L.REF_LAST_END], comp)], axis=-1)
        served_row = jnp.stack([
            jnp.maximum(directive["due"] + t.t_refi, vis),
            directive["end"], directive["target"],
            old_row[..., L.REF_DEBT], old_row[..., L.REF_LAST_END]], axis=-1)
        pending = directive["pending"]
        if jnp.ndim(pending):
            pending = pending[:, None]       # [B] lanes gate [B, REF_F] rows
        return jnp.where(pending, served_row, old_row)

    def ref_cmds(directive, hb, comp):
        """[R, CMD_F] OP_REF slots for the served step (emit_commands only).

        Modes 1/2/3/5 fire at most one burst per step, at the deadline
        (interval ``[due, end)``); subarray-granular modes carry the target
        subarray, bank-granular ones NEG. DARP fires up to three *chains*
        (idle drain / forced overflow / write shadow) whose lengths ride the
        aux lane — decode expands a chain of k into k bursts spaced tRFCpb.
        """
        i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731

        def rec(cond, cycle, sa_i, aux):
            return jnp.stack([jnp.where(cond, jnp.int32(L.OP_REF),
                                        jnp.int32(L.OP_NOP)),
                              i32(cycle), i32(hb), i32(sa_i), _NEG, i32(aux)])

        if refresh_mode != 4:
            target = (directive["target"] if refresh_mode in (2, 5) else _NEG)
            return rec(directive["pending"], directive["due"], target,
                       jnp.int32(1))[None]
        return jnp.stack([
            rec(directive["n_idle"] > 0, directive["launch"], _NEG,
                directive["n_idle"]),
            rec(directive["n_forced"] > 0, directive["forced_at"], _NEG,
                directive["n_forced"]),
            rec(directive["shadow"], comp, _NEG, jnp.int32(1)),
        ])

    return head_visibility, update_ref, ref_cmds


def _state1_init(n_banks: int, n_subarrays: int, t: DramTiming,
                 refresh_mode: int) -> dict:
    """Initial carry of the single-core (C == 1) fast-path step."""
    zero = jnp.int32(0)
    state0 = dict(bank=_engine._bank_state0(n_banks, n_subarrays),
                  ring=jnp.zeros((_RING,), jnp.int32),
                  vis_prev=zero, max_comp=zero)
    if refresh_mode:
        state0["ref"] = _refresh_table0(n_banks, t, refresh_mode)
    return state0


def _build_step1(policy: int, t: DramTiming, refresh_mode: int,
                 closed_row: bool, emit_commands: bool, mlp0, refresh_fns):
    """Build the single-core fast-path step function (carry, [XS_F] x row).

    With one core there is exactly one head request per step, so the
    serve order is statically program order: the request fields ride
    in as `xs` rows (zero gathers), the scheduler/argmin disappears
    (argmin over one element is 0), and the per-core vectors collapse
    to scalars. Bit-identical to the general path by construction —
    tests/test_controller.py pins 1-core mixes against `simulate`.

    Shared by the `lax.scan` in :func:`_simulate_controller` and the
    Pallas lane kernel's `fori_loop` (:mod:`repro.core.dram.pallas_step`)
    — ONE source of controller-step truth for both backends.
    """
    head_visibility, update_ref, ref_cmds = refresh_fns
    zero = jnp.int32(0)

    def step1(state, x):
            # x is one [XS_F] row of the packed request tensor: unpacking is
            # static indexing, fused into the step's arithmetic for free.
            i, hb, hs, hw = x[L.XS_IDX], x[L.XS_BANK], x[L.XS_SA], x[L.XS_ROW]
            hwr, hgap, hdep = x[L.XS_WR] != 0, x[L.XS_GAP], x[L.XS_DEP] != 0
            ring = state["ring"]
            rd = ring[jnp.stack([(i - 1) % _RING, (i - mlp0) % _RING])]
            comp_prev = rd[0]
            rob_lim = jnp.where(i >= mlp0, rd[1], 0)
            vis = jnp.maximum(state["vis_prev"] + hgap,
                              jnp.maximum(jnp.where(hdep, comp_prev, 0),
                                          rob_lim))
            if refresh_mode:
                ref_row = jax.lax.dynamic_slice(state["ref"], (hb, zero),
                                                (1, L.REF_F))[0]
                vis, directive = head_visibility(ref_row, vis, hs, hwr)
            req = dict(bank=hb, subarray=hs, row=hw, is_write=hwr, vis=vis)
            if refresh_mode:
                req["ref_pending"] = directive["pending"]
                req["ref_target"] = directive.get("target", zero)
            stepped = _engine._timing_step(policy, t, refresh_mode,
                                           state["bank"], req,
                                           closed_row=closed_row,
                                           emit=emit_commands)
            new_bank, comp = stepped[0], stepped[1]
            new = dict(state)
            new["bank"] = new_bank
            if refresh_mode:
                row_new = update_ref(ref_row, directive, vis, comp)
                new["ref"] = jax.lax.dynamic_update_slice(
                    state["ref"], row_new[None], (hb, zero))
            new["ring"] = ring.at[i % _RING].set(comp)
            new["vis_prev"] = vis
            new["max_comp"] = jnp.maximum(state["max_comp"], comp)
            if not emit_commands:
                return new, None
            cmds = stepped[2]
            if refresh_mode:
                cmds = jnp.concatenate([cmds, ref_cmds(directive, hb, comp)])
            return new, dict(cmds=cmds, comp=comp, core=zero, req=i)

    return step1


def _stateC_init(n_banks: int, n_subarrays: int, t: DramTiming,
                 refresh_mode: int, C: int) -> dict:
    """Initial carry of the general C-core step."""
    state0 = dict(
        bank=_engine._bank_state0(n_banks, n_subarrays),
        core=jnp.zeros((C, L.CORE_F), jnp.int32),
        comp_ring=jnp.zeros((C, _RING), jnp.int32),
    )
    if refresh_mode:
        state0["ref"] = _refresh_table0(n_banks, t, refresh_mode)
    return state0


def _build_stepC(policy: int, scheduler: int, t: DramTiming,
                 refresh_mode: int, closed_row: bool, emit_commands: bool,
                 reqs, mlp_window, rank, refresh_fns):
    """Build the general C-core step (carry, None) over the packed ``reqs``.

    ``reqs`` is the ONE packed [C, N, RQ_F] request tensor: each step
    gathers every head field with a single advanced-indexing gather
    instead of seven. Shared by the scan and the Pallas mix kernel,
    exactly like :func:`_build_step1`.
    """
    head_visibility, update_ref, ref_cmds = refresh_fns
    C, N = reqs.shape[0], reqs.shape[1]
    cores = jnp.arange(C, dtype=jnp.int32)
    zero = jnp.int32(0)

    def step(state, _):
        bank_st = state["bank"]
        core = state["core"]
        ptr = core[:, L.CORE_PTR]
        live = ptr < N
        p = jnp.minimum(ptr, N - 1)

        h = reqs[cores, p]                      # [C, RQ_F]: all head fields
        hb, hs, hw = h[:, L.RQ_BANK], h[:, L.RQ_SA], h[:, L.RQ_ROW]

        # ---- per-core visibility of the head request
        ring_idx = jnp.stack([(p - 1) % _RING, (p - mlp_window) % _RING],
                             axis=1)
        rd = state["comp_ring"][cores[:, None], ring_idx]   # [C, 2]
        comp_prev, rob_raw = rd[:, 0], rd[:, 1]
        rob_lim = jnp.where(p >= mlp_window, rob_raw, 0)
        vis = jnp.maximum(core[:, L.CORE_VIS_PREV] + h[:, L.RQ_GAP],
                          jnp.maximum(
                              jnp.where(h[:, L.RQ_DEP] != 0, comp_prev, 0),
                              rob_lim))
        if refresh_mode:
            vis, directive = head_visibility(
                jnp.moveaxis(state["ref"][hb], -1, 0), vis, hs,
                h[:, L.RQ_WR] != 0)

        # ---- scheduler: key the live heads, serve the argmin.
        # Under DARP the scheduler is refresh-aware: a bank one postpone
        # from a forced refresh drains its queued requests first.
        ref_debt = (state["ref"][hb, L.REF_DEBT] if refresh_mode == 4
                    else None)
        key = request_key(scheduler, bank_st, hb, hs, hw, vis, rank, C, live,
                          ref_debt=ref_debt,
                          ref_urgent=t.ref_postpone_max - 1,
                          hwr=h[:, L.RQ_WR] != 0)
        c = jnp.argmin(key).astype(jnp.int32)

        # ONE gather of the chosen head's fields + step bookkeeping
        # (lanes RQ_VIS / RQ_PTR / RQ_MAX_COMP appended after RQ_F).
        packed = jnp.concatenate(
            [h, vis[:, None], p[:, None], core[:, L.CORE_MAX_COMP][:, None]],
            axis=1)
        hc = jax.lax.dynamic_slice(packed, (c, zero), (1, L.RQ_EXT_F))[0]
        vis_c, pc, max_comp_c = hc[L.RQ_VIS], hc[L.RQ_PTR], hc[L.RQ_MAX_COMP]

        req = dict(
            bank=hc[L.RQ_BANK], subarray=hc[L.RQ_SA], row=hc[L.RQ_ROW],
            is_write=hc[L.RQ_WR] != 0, vis=vis_c,
        )
        if refresh_mode:
            # the chosen head's directive: one gather over the directive's
            # (mode-dependent, statically known) field set
            dkeys = sorted(directive)
            dmat = jnp.stack([directive[k].astype(jnp.int32) for k in dkeys],
                             axis=1)
            drow = jax.lax.dynamic_slice(dmat, (c, zero),
                                         (1, len(dkeys)))[0]
            directive_c = {k: drow[j] for j, k in enumerate(dkeys)}
            for k in ("pending", "shadow", "act"):
                if k in directive_c:
                    directive_c[k] = directive_c[k] != 0
            req["ref_pending"] = directive_c["pending"]
            req["ref_target"] = directive_c.get("target", zero)
        stepped = _engine._timing_step(policy, t, refresh_mode, bank_st, req,
                                       closed_row=closed_row,
                                       emit=emit_commands)
        new_bank, comp = stepped[0], stepped[1]

        new = dict(state)
        new["bank"] = new_bank
        if refresh_mode:
            hb_c = hc[L.RQ_BANK]
            old_row = jax.lax.dynamic_slice(state["ref"], (hb_c, zero),
                                            (1, L.REF_F))[0]
            row_new = update_ref(old_row, directive_c, vis_c, comp)
            new["ref"] = jax.lax.dynamic_update_slice(
                state["ref"], row_new[None], (hb_c, zero))
        # pc + 1 == ptr[c] + 1: the scan runs exactly C*N steps over C*N
        # requests, so argmin always lands on a live core (dead keys are
        # _DEAD) and the chosen ptr is never clamped by the min() above.
        core_row = jnp.stack([pc + 1, vis_c,
                              jnp.maximum(max_comp_c, comp)])
        new["core"] = jax.lax.dynamic_update_slice(core, core_row[None],
                                                   (c, zero))
        new["comp_ring"] = state["comp_ring"].at[c, pc % _RING].set(comp)
        if not emit_commands:
            return new, None
        # emission follows the CHOSEN head only (the step serves one request;
        # update_ref commits the same head's refresh row)
        cmds = stepped[2]
        if refresh_mode:
            cmds = jnp.concatenate(
                [cmds, ref_cmds(directive_c, hc[L.RQ_BANK], comp)])
        return new, dict(cmds=cmds, comp=comp, core=c, req=pc)

    return step


def _pack_reqs(bank, subarray, row, is_write, gap, dep):
    """Stack the six [..., N] request fields into one [..., N, RQ_F] tensor."""
    return jnp.stack([bank, subarray, row, is_write.astype(jnp.int32),
                      gap, dep.astype(jnp.int32)], axis=-1)


def _pack_xs(bank, subarray, row, is_write, gap, dep):
    """[N] request fields -> the C == 1 fast path's [N, XS_F] step rows."""
    return jnp.stack([jnp.arange(bank.shape[0], dtype=jnp.int32), bank,
                      subarray, row, is_write.astype(jnp.int32), gap,
                      dep.astype(jnp.int32)], axis=1)


@functools.partial(jax.jit, static_argnames=("policy", "scheduler", "n_banks",
                                             "n_subarrays", "timing",
                                             "refresh_mode", "closed_row",
                                             "emit_commands", "unroll"))
def _simulate_controller(policy: int, scheduler: int, n_banks: int,
                         n_subarrays: int, timing: DramTiming,
                         refresh_mode: int,
                         bank, subarray, row, is_write, gap, dep,  # [C, N]
                         mlp_window, rank,                         # [C]
                         closed_row: bool = False,
                         emit_commands: bool = False,
                         unroll: int = _SCAN_UNROLL):
    """Scan C*N controller steps; returns (SimResult, per-core max completion).

    With the static ``emit_commands`` flag a third element is returned: the
    scan's stacked per-step command log — ``dict(cmds=[steps, slots, CMD_F],
    comp=[steps], core=[steps], req=[steps])`` — which
    :mod:`repro.core.dram.commands` decodes into a :class:`CommandTrace`.
    The engine's slots are extended with the refresh commands this layer
    issues (``OP_REF``; DARP emits its idle-drain / forced / write-shadow
    burst chains as separate slots, chain length in the aux lane). The flag
    off is the exact historical trace — emission is pure Python branching.

    The step bodies and refresh closures live in the module-level builders
    (:func:`_build_step1` / :func:`_build_stepC` / :func:`_refresh_fns`):
    this function is the `lax.scan` instantiation, and the Pallas kernels
    (:mod:`repro.core.dram.pallas_step`) are `fori_loop` instantiations of
    the SAME builders — backend parity by construction.
    """
    t = timing
    C, N = bank.shape
    fns = _refresh_fns(policy, t, n_subarrays, refresh_mode, emit_commands)

    if C == 1:
        step1 = _build_step1(policy, t, refresh_mode, closed_row,
                             emit_commands, mlp_window[0], fns)
        state0 = _state1_init(n_banks, n_subarrays, t, refresh_mode)
        xs = _pack_xs(bank[0], subarray[0], row[0], is_write[0], gap[0],
                      dep[0])                                # [N, XS_F]
        final, ys = jax.lax.scan(step1, state0, xs, unroll=unroll)
        res = _engine.result_from_state(N, final["bank"]["scalars"],
                                        final["vis_prev"])
        if emit_commands:
            return res, final["max_comp"][None], ys
        return res, final["max_comp"][None]

    reqs = _pack_reqs(bank, subarray, row, is_write, gap, dep)
    step = _build_stepC(policy, scheduler, t, refresh_mode, closed_row,
                        emit_commands, reqs, mlp_window, rank, fns)
    state0 = _stateC_init(n_banks, n_subarrays, t, refresh_mode, C)
    final, ys = jax.lax.scan(step, state0, None, length=C * N, unroll=unroll)
    res = _engine.result_from_state(
        C * N, final["bank"]["scalars"], final["core"][:, L.CORE_VIS_PREV])
    if emit_commands:
        return res, final["core"][:, L.CORE_MAX_COMP], ys
    return res, final["core"][:, L.CORE_MAX_COMP]


@functools.partial(jax.jit, static_argnames=("policy", "n_banks",
                                             "n_subarrays", "timing",
                                             "mlp_static", "refresh_mode",
                                             "unroll"))
def _simulate_stacked_lanes(policy: int, n_banks: int, n_subarrays: int,
                            timing: DramTiming,
                            bank, subarray, row, is_write, gap, dep,  # [B, N]
                            mlp_window,                               # [B]
                            mlp_static: int | None = None,
                            refresh_mode: int = 0,
                            unroll: int = _LANES_UNROLL):
    """Lane-vectorized batched single-core controller (ONE scan, B lanes).

    The historical batched path is ``vmap`` over the C == 1 fast path —
    correct, but it turns every step into B-way batched versions of the
    *per-trace* ops: the ``[ns + 1, SA_F]`` block gather/scatter becomes a
    ``[B, ns + 1, SA_F]`` gather/scatter and the full-block rebuild costs
    O(B * ns) per step. This path restructures instead of batching: one
    scan whose carry holds all B lanes' state side by side, with the
    row-wise step math (:func:`engine._step_math_lanes`) touching only the
    three ``[B, SA_F]`` rows a step can change. The scan step is trimmed to
    the sequentially-dependent minimum three ways:

    * **one scatter** — the three changed rows go back as a single
      scatter-ADD of deltas (``new - old``) at indices ``[so, s, ns]``: add
      is well-defined under the duplicate index ``so == s`` that arises
      when the other-row gate is off (its delta is exactly zero then),
      which a 3-deep ``.set`` sequence had to order around;
    * **counters out of the loop** — SimResult's ten counters are pure
      functions of the per-step flags, so the scan just stacks the raw
      flags (``ys``) and the counters are reconstructed afterwards in one
      vectorized O(N·B) pass (sums / running extrema are order-insensitive
      mod-2^32, so bit-parity holds);
    * **ring as slices** — the completion ring is carried ``[_RING, B]``
      (lane-minor) so the per-step write is always a contiguous row
      ``dynamic_update_slice``. When every lane shares one ``mlp_window``
      (the overwhelmingly common stacked case, checked host-side by the
      caller and passed as static ``mlp_static``) the ROB read is a
      contiguous row ``dynamic_slice`` too; per-lane windows fall back to
      a cross-lane gather on the read only. The ``i - 1`` ring read of the
      reference is carried directly as ``comp_last`` either way.

    **Refresh** (``refresh_mode != 0``, static): a pending refresh closes
    every row of the bank (bank-granular modes 1, 3, 4) or the refreshed
    subarray's (modes 2, 5), so a step may change more than three rows.
    The bank's refresh row then rides in the plane as row ``ns + 1``
    (``REF_F == SA_F``), and each step gathers the served bank's whole
    ``[B, ns + 2, SA_F]`` block with ONE gather, gates the heads with
    :func:`_refresh_fns`' ``head_visibility``, runs
    :func:`engine._step_math` (vmapped over the lanes) on the block, commits
    the refresh row with ``update_ref``, and writes the block back with ONE
    unique-indices scatter (a ``(lane, bank)`` pair is unique per step).
    The closures and the block math are the ones ``_simulate_controller``
    and the Pallas kernels run, and the counters accumulate in the block
    math's ``[B, SC_F]`` scalar pack as they do there. (A row-wise variant
    on :func:`engine._step_math_lanes` with the closure as a mask measured
    12.0 us a step on a TPU v5e against this one's 8.6, MASA, 32 lanes,
    DARP.) With refresh off none of this is traced: the program is the
    three-row one above.

    Eligibility is ``engine.runs_lanes`` (open-row policy, no command
    emission, the scan backend); ``engine.simulate_stacked`` dispatches
    here and falls back to the vmapped general path otherwise. The C == 1
    scheduler degeneration applies per lane (program order), so no
    scheduler argument. Bit-identical to the vmapped path — the stacked
    parity suites pin it against per-trace ``simulate`` on every combo.
    """
    t = timing
    B, N = bank.shape
    ns = n_subarrays
    is_masa = policy == Policy.MASA
    lanes = jnp.arange(B, dtype=jnp.int32)
    zero = jnp.int32(0)
    base = _engine._bank_state0(n_banks, ns)
    uniform = mlp_static is not None
    plane0 = base["sa"]
    if refresh_mode:
        head_visibility, update_ref, _ = _refresh_fns(policy, t, ns,
                                                      refresh_mode, False)
        step_math = jax.vmap(functools.partial(_engine._step_math, policy, t,
                                               refresh_mode))
        # the refresh table rides in the plane as row ns + 1
        plane0 = jnp.concatenate(
            [plane0, _refresh_table0(n_banks, t, refresh_mode)[:, None]], 1)
    state0 = dict(
        sa=jnp.broadcast_to(plane0, (B, n_banks) + plane0.shape[1:]),
        act_hist=jnp.zeros((B, 4), jnp.int32),
        col=dict(col_last=jnp.full((B,), -(10 ** 6), jnp.int32),
                 col_last_wr=jnp.zeros((B,), bool),
                 wr_data_end=jnp.zeros((B,), jnp.int32),
                 bus_free=jnp.zeros((B,), jnp.int32)),
        ring=jnp.zeros((_RING, B), jnp.int32),
        comp_last=jnp.zeros((B,), jnp.int32),
        vis_prev=jnp.zeros((B,), jnp.int32),
    )
    if refresh_mode:
        # the block math keeps the channel scalars and the counters
        state0.pop("col")
        state0["sc"] = jnp.broadcast_to(base["scalars"], (B, L.SC_F))
    mlp = jnp.asarray(mlp_window, jnp.int32)
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    # ONE packed [N, B, XS_F - 1] request tensor (XS_BANK..XS_DEP order,
    # one lane left of the fast path's xs): the scan reads one buffer per
    # step and the per-field unpack slices fuse into the step's arithmetic,
    # instead of seven per-leaf dynamic-slice reads.
    xr = jnp.stack([bank.T, subarray.T, row.T, i32(is_write.T), gap.T,
                    i32(dep.T)], axis=-1)
    xs = (jnp.arange(N, dtype=jnp.int32), xr)
    # per-step facts for the post-scan counter pass, packed the same way
    # (one [B, YS_F] stack per step -> one buffer write instead of six)
    Y_TCOL, Y_COMP, Y_VIS, Y_HIT, Y_PREOWN, Y_EXTRA, YS_F = range(7)

    def visible(state, i, xrow):
        """The step's request fields and their visibility cycles, [B]."""
        hb, hs, hw = xrow[:, 0], xrow[:, 1], xrow[:, 2]
        hwr, hgap, hdep = xrow[:, 3] != 0, xrow[:, 4], xrow[:, 5]
        ring = state["ring"]
        if uniform:
            rob_raw = jax.lax.dynamic_slice(
                ring, ((i - mlp_static) % _RING, zero), (1, B))[0]
            rob_lim = jnp.where(i >= mlp_static, rob_raw, 0)
        else:
            rob_raw = ring[(i - mlp) % _RING, lanes]
            rob_lim = jnp.where(i >= mlp, rob_raw, 0)
        vis = jnp.maximum(state["vis_prev"] + hgap,
                          jnp.maximum(jnp.where(hdep != 0,
                                                state["comp_last"], 0),
                                      rob_lim))
        return hb, hs, hw, hwr, vis

    def step(state, x):
        i, xrow = x
        hb, hs, hw, hwr, vis = visible(state, i, xrow)
        ring = state["ring"]
        sa = state["sa"]
        if is_masa:
            # no cross-subarray PRE under MASA: the two touched rows (own
            # subarray + bank-vector) are known up front -> ONE gather, and
            # the same index matrix drives the scatter back
            rows = jnp.stack([hs, jnp.full_like(hs, ns)], axis=1)    # [B, 2]
            pair = sa[lanes[:, None], hb[:, None], rows]
            own, bv, oth = pair[:, 0], pair[:, 1], None
        else:
            bv = sa[lanes, hb, ns]                          # [B, SA_F]
            os_ = bv[:, L.BK_OPEN_SA]
            so = jnp.where(os_ != _NEG, os_, 0)             # gather-safe
            rows = jnp.stack([so, hs], axis=1)
            pair = sa[lanes[:, None], hb[:, None], rows]     # [B, 2, SA_F]
            oth, own = pair[:, 0], pair[:, 1]
            rows = jnp.concatenate([rows, jnp.full_like(hs, ns)[:, None]], 1)
        req = dict(subarray=hs, row=hw, is_write=hwr, vis=vis)
        own_new, oth_new, bv_new, act_hist, col, comp, flags = \
            _engine._step_math_lanes(policy, t, own, oth, bv,
                                     state["act_hist"], state["col"], req)
        if is_masa:
            # (lane, bank, row) triples are globally unique here (hs != ns
            # always), so a direct unique-indices set is legal and skips the
            # scatter's duplicate handling
            upd = jnp.stack([own_new, bv_new], axis=1)
            sa = sa.at[lanes[:, None], hb[:, None], rows].set(
                upd, mode="promise_in_bounds", unique_indices=True)
            extra = flags["sasel"]
        else:
            # so == hs duplicates arise when the other-row gate is off; the
            # gate-off delta is exactly zero, so scatter-ADD is well-defined
            # where an ordered .set sequence would be needed otherwise
            upd = jnp.stack([oth_new - oth, own_new - own, bv_new - bv],
                            axis=1)
            sa = sa.at[lanes[:, None], hb[:, None], rows].add(
                upd, mode="promise_in_bounds")
            extra = flags["pre_oth"]
        ring = jax.lax.dynamic_update_slice(ring, comp[None],
                                            (i % _RING, zero))
        new = dict(sa=sa, act_hist=act_hist, col=col, ring=ring,
                   comp_last=comp, vis_prev=vis)
        y = jnp.stack([flags["t_col"], comp, vis, i32(flags["hit"]),
                       i32(flags["pre_own"]), i32(extra)], axis=1)
        return new, y

    def step_refresh(state, x):
        i, xrow = x
        hb, hs, hw, hwr, vis = visible(state, i, xrow)
        sa = state["sa"]
        blk = sa[lanes, hb]                          # [B, ns + 2, SA_F]
        ref_row = blk[:, ns + 1]
        vis, directive = head_visibility(jnp.moveaxis(ref_row, -1, 0), vis,
                                         hs, hwr)
        req = dict(bank=hb, subarray=hs, row=hw, is_write=hwr, vis=vis,
                   ref_pending=directive["pending"],
                   ref_target=directive.get("target", jnp.zeros_like(hs)))
        bk, act_hist, sc, comp = step_math(blk[:, :ns + 1], state["act_hist"],
                                           state["sc"], req)
        blk = jnp.concatenate(
            [bk, update_ref(ref_row, directive, vis, comp)[:, None]], 1)
        sa = sa.at[lanes, hb].set(blk, mode="promise_in_bounds",
                                  unique_indices=True)
        ring = jax.lax.dynamic_update_slice(state["ring"], comp[None],
                                            (i % _RING, zero))
        return dict(sa=sa, act_hist=act_hist, sc=sc, ring=ring,
                    comp_last=comp, vis_prev=vis), None

    if refresh_mode:
        final, _ = jax.lax.scan(step_refresh, state0, xs, unroll=unroll)
        return jax.vmap(functools.partial(_engine.result_from_state, N))(
            final["sc"], final["vis_prev"])

    final, ys = jax.lax.scan(step, state0, xs, unroll=unroll)  # ys [N, B, YS_F]

    # ---- counter reconstruction (vectorized over [N, B], once) ------------
    iw = is_write.T != 0
    t_col, comp, vis = ys[..., Y_TCOL], ys[..., Y_COMP], ys[..., Y_VIS]
    hit, pre_own, extra = ys[..., Y_HIT], ys[..., Y_PREOWN], ys[..., Y_EXTRA]
    n_wr = jnp.sum(i32(iw), axis=0)
    n_hit = jnp.sum(hit, axis=0)
    n_pre_own = jnp.sum(pre_own, axis=0)
    zcol = jnp.zeros((B,), jnp.int32)
    n_pre_oth = zcol if is_masa else jnp.sum(extra, axis=0)
    n_sasel = jnp.sum(extra, axis=0) if is_masa else zcol
    # subarray-open-count integral: open count BEFORE step i is the
    # exclusive cumsum of the per-step deltas; the integration checkpoint
    # (reference's SC_LAST_OPEN_TIME) is the running max of t_col
    delta = (1 - hit) - pre_own - (0 if is_masa else extra)
    zrow = jnp.zeros((1, B), jnp.int32)
    oc_before = jnp.concatenate([zrow, jnp.cumsum(delta, axis=0)[:-1]], 0)
    open_prev = jnp.concatenate([zrow, jax.lax.cummax(t_col, axis=0)[:-1]], 0)
    sa_open = jnp.sum(jnp.maximum(oc_before - 1, 0)
                      * jnp.maximum(t_col - open_prev, 0), axis=0)
    return _engine.SimResult(
        total_cycles=jnp.maximum(jnp.max(comp, axis=0), final["vis_prev"]),
        n_requests=jnp.full((B,), N, jnp.int32),
        n_act=jnp.int32(N) - n_hit,
        n_pre=n_pre_oth + n_pre_own,
        n_rd=jnp.int32(N) - n_wr, n_wr=n_wr,
        n_sasel=n_sasel, n_hit=n_hit,
        sum_latency=jnp.sum(jnp.where(iw, 0, comp - vis), axis=0),
        n_reads=jnp.int32(N) - n_wr,
        sa_open_cycles=sa_open)


@functools.partial(jax.jit, static_argnames=("policy", "scheduler", "n_banks",
                                             "n_subarrays", "timing",
                                             "refresh_mode", "closed_row",
                                             "unroll"))
def _simulate_mixes_lanes(policy: int, scheduler: int, n_banks: int,
                          n_subarrays: int, timing: DramTiming,
                          refresh_mode: int,
                          bank, subarray, row, is_write, gap, dep,  # [M, C, N]
                          mlp_window, rank,                         # [M, C]
                          closed_row: bool = False,
                          unroll: int = _LANES_UNROLL):
    """Lane-major batched C-core controller (ONE scan, M mixes on lanes).

    ``jax.vmap`` of :func:`_simulate_controller` over M mixes gives every
    store of :func:`_build_stepC` (the bank block, the ``core`` row, the
    completion-ring slot, the refresh row) a per-lane index, and XLA on a
    TPU lowers those per-lane ``dynamic_update_slice``s as serial loops
    over the lanes (31.9 us a step for four 4-core mixes on a TPU v5e,
    11.8 here; PERF.md). This scan carries all M mixes side by side, as
    :func:`_simulate_stacked_lanes` does for 1-core lanes with refresh on:
    the plane ``[M, nb, ns + 1 (+1), SA_F]`` (the bank's refresh row rides
    as row ``ns + 1``), the scalar packs ``[M, SC_F]`` and ``[M, 4]``, the
    per-core ``[M, C, CORE_F]`` and the rings ``[M, C, _RING]``. A step:

    * ONE gather of every lane's C head rows from the packed
      ``[M, C, N, RQ_F]`` request tensor, then per-core visibility and the
      refresh gate (:func:`_refresh_fns`' ``head_visibility``) as in
      :func:`_build_stepC`;
    * the scheduler keys (``schedulers.request_key``, vmapped over the
      lanes), a per-lane ``argmin`` and ONE ``take_along_axis`` of the
      chosen head's fields, bookkeeping and refresh directive;
    * ONE gather of each lane's served bank block, ``jax.vmap`` of
      :func:`engine._step_math` on it (the counters accumulate in its
      scalar pack), ``update_ref`` on the refresh row, and ONE
      unique-indices scatter of the block back (a ``(lane, bank)`` pair is
      unique per step);
    * the ``core`` row and the ring slot of the served core written by a
      one-hot ``where`` over the C cores and the ring, not by per-lane
      stores.

    The step math, the scheduler keys and the refresh closures are the
    ones ``_simulate_controller`` and the Pallas kernels run, so the
    results are bit-identical to ``jax.vmap`` of it: every scheduler,
    policy, refresh mode and row policy (tests/test_controller.py,
    tests/test_packed_state.py). Returns ``(SimResult, core_cycles)`` with
    a leading ``[M]`` axis, as the vmapped controller does; there is no
    command emission here (``commands.simulate_mix_commands`` keeps the
    per-mix controller).
    """
    t = timing
    M, C, N = bank.shape
    ns = n_subarrays
    lanes = jnp.arange(M, dtype=jnp.int32)
    cores = jnp.arange(C, dtype=jnp.int32)
    slots = jnp.arange(_RING, dtype=jnp.int32)
    head_visibility, update_ref, _ = _refresh_fns(policy, t, ns,
                                                  refresh_mode, False)
    step_math = jax.vmap(functools.partial(_engine._step_math, policy, t,
                                           refresh_mode,
                                           closed_row=closed_row))

    def keys(sa, sc, hb, hs, hw, vis, rank_, live, hwr, ref_debt):
        return request_key(scheduler, dict(sa=sa, scalars=sc), hb, hs, hw,
                           vis, rank_, C, live, ref_debt=ref_debt,
                           ref_urgent=t.ref_postpone_max - 1, hwr=hwr)

    base = _engine._bank_state0(n_banks, ns)
    plane0 = base["sa"]
    if refresh_mode:
        # the refresh table rides in the plane as row ns + 1
        plane0 = jnp.concatenate(
            [plane0, _refresh_table0(n_banks, t, refresh_mode)[:, None]], 1)
    state0 = dict(
        sa=jnp.broadcast_to(plane0, (M,) + plane0.shape),
        sc=jnp.broadcast_to(base["scalars"], (M, L.SC_F)),
        act_hist=jnp.zeros((M, 4), jnp.int32),
        core=jnp.zeros((M, C, L.CORE_F), jnp.int32),
        comp_ring=jnp.zeros((M, C, _RING), jnp.int32),
    )
    # ONE packed [M, C, N, RQ_F] request tensor
    reqs = _pack_reqs(bank, subarray, row, is_write, gap, dep)
    mlp = jnp.asarray(mlp_window, jnp.int32)

    def step(state, _):
        sa, core = state["sa"], state["core"]
        ptr = core[..., L.CORE_PTR]                             # [M, C]
        live = ptr < N
        p = jnp.minimum(ptr, N - 1)
        h = reqs[lanes[:, None], cores, p]                      # [M, C, RQ_F]
        hb, hs, hw = h[..., L.RQ_BANK], h[..., L.RQ_SA], h[..., L.RQ_ROW]
        hwr = h[..., L.RQ_WR] != 0

        # ---- per-core visibility of the head request
        ring_idx = jnp.stack([(p - 1) % _RING, (p - mlp) % _RING], axis=-1)
        rd = state["comp_ring"][lanes[:, None, None], cores[:, None],
                                ring_idx]                       # [M, C, 2]
        rob_lim = jnp.where(p >= mlp, rd[..., 1], 0)
        vis = jnp.maximum(core[..., L.CORE_VIS_PREV] + h[..., L.RQ_GAP],
                          jnp.maximum(
                              jnp.where(h[..., L.RQ_DEP] != 0, rd[..., 0], 0),
                              rob_lim))
        ext = [h]
        ref_debt = None
        if refresh_mode:
            refb = sa[lanes[:, None], hb, ns + 1]               # [M, C, REF_F]
            vis, directive = head_visibility(jnp.moveaxis(refb, -1, 0), vis,
                                             hs, hwr)
            if refresh_mode == 4:
                ref_debt = refb[..., L.REF_DEBT]
            dkeys = sorted(directive)

        # ---- scheduler: key the live heads, serve each lane's argmin
        key = jax.vmap(keys)(sa, state["sc"], hb, hs, hw, vis, rank, live,
                             hwr, ref_debt)
        c = jnp.argmin(key, axis=1).astype(jnp.int32)          # [M]

        # ONE gather of the chosen heads' fields + step bookkeeping (lanes
        # RQ_VIS / RQ_PTR / RQ_MAX_COMP after RQ_F) + refresh directive
        ext += [vis[..., None], p[..., None],
                core[..., L.CORE_MAX_COMP][..., None]]
        if refresh_mode:
            ext += [directive[k].astype(jnp.int32)[..., None] for k in dkeys]
        hc = jnp.take_along_axis(jnp.concatenate(ext, axis=-1),
                                 c[:, None, None], axis=1)[:, 0]
        hb_c, vis_c = hc[:, L.RQ_BANK], hc[:, L.RQ_VIS]
        pc, max_comp_c = hc[:, L.RQ_PTR], hc[:, L.RQ_MAX_COMP]
        req = dict(bank=hb_c, subarray=hc[:, L.RQ_SA], row=hc[:, L.RQ_ROW],
                   is_write=hc[:, L.RQ_WR] != 0, vis=vis_c)
        if refresh_mode:
            directive_c = {k: hc[:, L.RQ_EXT_F + j]
                           for j, k in enumerate(dkeys)}
            for k in ("pending", "shadow", "act"):
                if k in directive_c:
                    directive_c[k] = directive_c[k] != 0
            req["ref_pending"] = directive_c["pending"]
            req["ref_target"] = directive_c.get("target",
                                                jnp.zeros_like(hb_c))

        # ---- the served bank's block: one gather, the math, one scatter
        blk = sa[lanes, hb_c]                    # [M, ns + 1 (+1), SA_F]
        bk, act_hist, sc, comp = step_math(blk[:, :ns + 1], state["act_hist"],
                                           state["sc"], req)
        if refresh_mode:
            bk = jnp.concatenate(
                [bk, update_ref(blk[:, ns + 1], directive_c, vis_c,
                                comp)[:, None]], 1)
        sa = sa.at[lanes, hb_c].set(bk, mode="promise_in_bounds",
                                    unique_indices=True)

        # ---- the served core's row and ring slot: one-hot over C (and the
        # ring). pc + 1 == ptr[c] + 1, as in _build_stepC.
        served = cores == c[:, None]                            # [M, C]
        core_row = jnp.stack([pc + 1, vis_c, jnp.maximum(max_comp_c, comp)],
                             axis=-1)                           # [M, CORE_F]
        core = jnp.where(served[..., None], core_row[:, None], core)
        ring = jnp.where(served[..., None]
                         & (slots == (pc % _RING)[:, None, None]),
                         comp[:, None, None], state["comp_ring"])
        return dict(sa=sa, sc=sc, act_hist=act_hist, core=core,
                    comp_ring=ring), None

    final, _ = jax.lax.scan(step, state0, None, length=C * N, unroll=unroll)
    res = jax.vmap(functools.partial(_engine.result_from_state, C * N))(
        final["sc"], final["core"][..., L.CORE_VIS_PREV])
    return res, final["core"][..., L.CORE_MAX_COMP]
