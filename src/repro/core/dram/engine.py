"""Bank/subarray DRAM timing state machine in pure JAX (layer 1 of 3).

This module owns the *device*: given one already-scheduled request and the
cycle at which the controller exposes it (``vis``), ``_timing_step`` computes
the issue time of every DRAM command the request needs (PRE / ACT / SA_SEL /
RD / WR) under the active policy's timing rules, updates per-bank /
per-subarray timing state, and returns the request's completion time.

Everything about *which* request is served next — per-core visibility,
completion rings, request scheduling, refresh bookkeeping — lives one layer
up in :mod:`repro.core.dram.controller`; the pluggable scheduling disciplines
live in :mod:`repro.core.dram.schedulers`. The ``simulate*`` entry points
here are thin single-core (1-core-mix) instantiations of the controller.

State layout (:mod:`repro.core.dram.state_layout`): the per-subarray timing
plane AND the per-bank vector state ride in ONE packed ``[nb, ns + 1, SA_F]``
int32 tensor; a ``_timing_step`` gathers the target bank's ``[ns + 1, SA_F]``
block with a single ``dynamic_slice``, computes on scalars / ``[ns + 1]``
vectors, and scatters the block back with a single ``dynamic_update_slice``
— O(S) work per scan step instead of O(B*S) full-array copies per
conditional update (see docs/performance.md for the gather/scatter contract
and the measured effect).

Policy timing semantics (`t_*` are issue cycles; see timing.py for constants):

  same-subarray conflict (all policies):   PRE(s) -> tRP -> ACT(s) -> tRCD -> COL
  cross-subarray conflict, open s', target s:
    BASELINE:  ACT(s) >= PRE(s') + tRP                (bank-level serialization)
    SALP-1:    ACT(s) >= PRE(s') + 1                  (tRP overlapped)
    SALP-2:    ACT(s) independent of PRE(s');
               COL(s) >= PRE(s') + 1                  (write recovery overlapped)
    MASA:      s' stays open; no PRE at all; COL needs SA_SEL if the bank's
               designated subarray != s. A row still open in ANY subarray is a
               row-buffer hit (SA_SEL + COL, no ACT) — the paper's locality win.

Write recovery: PRE(x) >= last write data end in x + tWR. In the baseline this
delays the next ACT to the whole bank; under SALP-2/MASA it only delays x.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.dram import registry
from repro.core.dram import state_layout as L
from repro.core.dram.policies import Policy
from repro.core.dram.refresh import RefreshPolicy
from repro.core.dram.schedulers import Scheduler
from repro.core.dram.timing import DramTiming, DDR3_1066, MEMTECHS
from repro.core.dram.trace import Trace, to_ideal, stack_traces

_NEG = L.NEG
_RING = 64  # completion ring size; controller.validate_mlp_window enforces
            # mlp_window < _RING at every simulate* entry

#: Valid ``SimConfig.backend`` values (see the field's docstring).
BACKENDS = frozenset({"scan", "pallas", "pallas-interpret"})

registry.register("backend", tuple(sorted(BACKENDS)))

#: Why ``SimConfig`` refuses ``backend="pallas"``: the TPU compiler (Mosaic,
#: JAX 0.9, for a v5e) refuses the fused kernels of
#: :mod:`repro.core.dram.pallas_step` in two places, in this order.
PALLAS_COMPILE_ERROR = (
    "backend='pallas' does not compile for a TPU. Mosaic refuses the "
    "kernels' (1, 1) blocks over (B, 1) arrays: 'The Pallas TPU lowering "
    "currently requires that the last two dimensions of your block shape "
    "are divisible by 8 and 128 respectively, or be equal to the respective "
    "dimensions of the overall array'. With 3-D blocks it then refuses the "
    "kernel body: 'Unimplemented primitive in Pallas TPU lowering for "
    "KernelType.TC: scatter' (the completion-ring update "
    "ring.at[i % _RING].set(comp) of controller._build_step1). Use "
    "backend='scan' (the default) on the chip; backend='pallas-interpret' "
    "is the CPU parity reference.")


@dataclasses.dataclass(frozen=True)
class SimConfig:
    n_banks: int = 8
    n_subarrays: int = 8
    timing: DramTiming = DDR3_1066
    # DEPRECATED refresh pair (kept as a shim): ``refresh``/``dsarp`` map onto
    # the ``refresh_policy`` ladder below (``refresh=True`` == "all_bank",
    # ``refresh=True, dsarp=True`` == "dsarp"). ``__post_init__`` CONSUMES
    # them: the policy is canonicalized into ``refresh_policy`` and both
    # fields are reset to ``None``, so a config built either way is
    # field-identical (same cache keys, same golden-fixture counters) and
    # ``dataclasses.replace`` can never smuggle stale derived booleans into
    # a later canonicalization. An EXPLICIT boolean that contradicts
    # ``refresh_policy`` — e.g. ``dataclasses.replace(cfg, refresh=False)``
    # on a refresh-enabled config — raises instead of being silently
    # re-derived (use ``refresh_policy="none"`` to turn refresh off). Read
    # ``refresh_policy`` / ``refresh_mode``, never these fields.
    refresh: bool | None = None
    dsarp: bool | None = None
    # Row policy (paper Sec. 9.3 sensitivity): "open" keeps rows latched after
    # a column access (row-buffer hits possible); "closed" auto-precharges
    # after every access (no hits, but no conflict serialization either) —
    # MASA's locality benefit exists only under the open-row policy.
    row_policy: str = "open"
    # Request scheduler (controller layer). With a single core every
    # discipline degenerates to program order (there is only one head
    # request), so the default is inert for `simulate`; in multicore it
    # selects among the cores' head requests (paper Sec. 4 / 9.3).
    scheduler: Scheduler = Scheduler.FCFS
    # Address-mapping spec (frontend layer, docs/address-mapping.md): how
    # physical addresses decode into (bank, subarray, row). The timing core
    # never reads it — it binds at trace generation / ingestion
    # (repro.experiments.runner.trace_for, Trace.from_file) — but it lives
    # here so sweeps treat layout as an ordinary config axis and result-cache
    # keys distinguish mappings. "golden" is the pinned historical default.
    mapping: str = "golden"
    # Refresh-policy ladder (paper Sec. 6.1; Chang et al. HPCA'14 — see
    # :mod:`repro.core.dram.refresh` and docs/refresh.md):
    #   "none"     — refresh off,
    #   "all_bank" — blocking REFab burst (tRFC) on the per-bank deadline,
    #   "per_bank" — REFpb: the shorter per-bank burst (tRFCpb),
    #   "darp"     — REFpb + dynamic pull-in / postpone / write-shadow
    #                scheduling inside the 8-deep spec window,
    #   "sarp"     — REFpb occupying ONE subarray; other subarrays of the
    #                bank proceed even without MASA,
    #   "dsarp"    — historical DSARP (tRFC burst one subarray at a time;
    #                only MASA serves around it).
    refresh_policy: str = "none"
    # Command-stream export (docs/commands.md): when True, the controller
    # scan additionally emits the packed per-step command log that
    # :mod:`repro.core.dram.commands` decodes into a CommandTrace and
    # :mod:`repro.core.dram.checker` verifies against the JEDEC rule table.
    # A *static* axis (new compiled program), consumed by the
    # ``simulate_commands`` entry points; the default-off path traces the
    # exact op graph it always did — bit-identical results, zero overhead.
    emit_commands: bool = False
    # Execution backend for the controller scan (docs/kernels.md):
    #   "scan"             — the packed `lax.scan` (XLA). The batched entry
    #                        points additionally take the lane-vectorized
    #                        single-scan fast path when eligible (refresh
    #                        off, open rows); bit-identical either way.
    #   "pallas"           — the fused Pallas kernel
    #                        (:mod:`repro.core.dram.pallas_step`) compiled
    #                        by Mosaic. REFUSED at construction: the TPU
    #                        compiler rejects the kernel
    #                        (``PALLAS_COMPILE_ERROR`` says where).
    #   "pallas-interpret" — the same kernel with ``interpret=True``: batch
    #                        dim as the kernel grid axis, the packed state
    #                        carried across all steps, run through XLA on
    #                        the CPU. The parity contract is enforced on
    #                        this path.
    # A *static* axis: part of cache keys / bucket signatures like every
    # other field. The Pallas backends refuse ``emit_commands`` (the kernel
    # carries no per-step command log) — use backend="scan" for exports.
    backend: str = "scan"
    # Memory-technology pack (docs/memtech.md): which per-technology timing
    # pack backs the simulation —
    #   "ddr3"     — the paper's DDR3-1066 baseline (DDR3_1066, bit-pinned),
    #   "lpddr4"   — LPDDR4-3200-class pack, per-bank-refresh-centric (the
    #                native home of the REFpb/DARP/SARP ladder),
    #   "pcm_palp" — Phase Change Memory after PALP (arXiv 1908.07966):
    #                asymmetric read/write latencies (slow array writes keep
    #                the partition busy) and NO refresh — any
    #                ``refresh_policy`` but "none" raises.
    # When ``timing`` is left at the DDR3_1066 default, ``__post_init__``
    # resolves it to the pack (``DramTiming.preset(memtech)``); an explicit
    # ``timing`` is kept as-is, so sweeps can still override individual
    # constants with ``dataclasses.replace`` on a pack. A *static* axis like
    # every other field: part of cache keys and bucket signatures.
    memtech: str = "ddr3"

    def __post_init__(self) -> None:
        registry.resolve("backend", self.backend,
                         valid=tuple(sorted(BACKENDS)))
        if self.backend == "pallas":
            raise ValueError(PALLAS_COMPILE_ERROR)
        # Resolve the memtech spec first (typos raise the shared registry
        # error), then bind the technology's timing pack unless the caller
        # pinned an explicit DramTiming.
        tech = registry.resolve("memtech", str(self.memtech).lower(),
                                valid=tuple(MEMTECHS))
        object.__setattr__(self, "memtech", tech)
        if tech != "ddr3" and self.timing == DDR3_1066:
            object.__setattr__(self, "timing", MEMTECHS[tech])
        # Canonicalize the deprecated boolean pair into refresh_policy and
        # null the pair, so semantically-equal configs are field-identical:
        # astuple/asdict — and therefore result-cache keys and vmap bucket
        # signatures — cannot tell them apart, and replace() round-trips.
        rp = RefreshPolicy.from_spec(self.refresh_policy)
        if rp == RefreshPolicy.NONE:
            if self.refresh:
                rp = RefreshPolicy.DSARP if self.dsarp else RefreshPolicy.ALL_BANK
            elif self.dsarp:
                raise ValueError("dsarp=True requires refresh=True (or use "
                                 "refresh_policy='dsarp')")
        else:
            expect = (True, rp == RefreshPolicy.DSARP)
            if ((self.refresh is not None and self.refresh != expect[0])
                    or (self.dsarp is not None and self.dsarp != expect[1])):
                raise ValueError(
                    f"refresh_policy={rp.spec!r} conflicts with the "
                    f"deprecated pair refresh={self.refresh}, "
                    f"dsarp={self.dsarp}; the booleans are derived from "
                    f"refresh_policy — drop them, and use "
                    f"refresh_policy='none'/'dsarp' instead of toggling "
                    f"refresh/dsarp on an existing config")
        object.__setattr__(self, "refresh_policy", rp.spec)
        object.__setattr__(self, "refresh", None)
        object.__setattr__(self, "dsarp", None)
        # PCM cells are non-volatile at DRAM retention scales: there IS no
        # refresh to model, and the pcm_palp pack zeroes the refresh fields
        # — silently running a refresh ladder against it would divide the
        # schedule by a zero interval. Conflicts raise, loudly.
        if self.memtech == "pcm_palp" and rp != RefreshPolicy.NONE:
            raise ValueError(
                f"memtech='pcm_palp' forces refresh_policy='none' (PCM "
                f"cells need no refresh), but got "
                f"refresh_policy={rp.spec!r}; drop the refresh_policy (or "
                f"sweep it only over the DRAM memtechs)")

    @classmethod
    def for_tech(cls, memtech: str, *, density_gb: int | None = None,
                 t_refi: int | None = None, **overrides) -> "SimConfig":
        """Canonical per-technology constructor.

        Builds the config with ``timing = DramTiming.preset(memtech,
        density_gb=..., t_refi=...)`` — the blessed way to get a
        density-scaled pack without hand-editing tRFC tables (what
        refresh_bench used to inline). ``overrides`` are ordinary
        ``SimConfig`` fields; passing ``timing`` explicitly is rejected
        (use ``SimConfig(memtech=..., timing=...)`` directly for that).
        """
        if "timing" in overrides:
            raise ValueError(
                "SimConfig.for_tech builds the timing pack itself; pass "
                "SimConfig(memtech=..., timing=...) to pin explicit timing")
        timing = DramTiming.preset(memtech, density_gb=density_gb,
                                   t_refi=t_refi)
        return cls(memtech=str(memtech).lower(), timing=timing, **overrides)

    def geometry_for(self, policy: Policy) -> tuple[int, int]:
        """IDEAL turns every subarray into a real bank."""
        if policy == Policy.IDEAL:
            return self.n_banks * self.n_subarrays, 1
        return self.n_banks, self.n_subarrays

    @property
    def refresh_mode(self) -> int:
        """Static engine/controller mode: the ``RefreshPolicy`` enum value
        (0 off, 1 REFab, 2 DSARP, 3 REFpb, 4 DARP, 5 SARP)."""
        return int(RefreshPolicy.from_spec(self.refresh_policy))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SimResult:
    """Aggregate counters from one simulation (all jnp scalars / [W]-vectors)."""
    total_cycles: jax.Array     # end-to-end DRAM cycles for the trace
    n_requests: jax.Array
    n_act: jax.Array
    n_pre: jax.Array
    n_rd: jax.Array
    n_wr: jax.Array
    n_sasel: jax.Array
    n_hit: jax.Array            # column served without an ACT (row-buffer hit)
    sum_latency: jax.Array      # sum of (completion - visible) for reads
    n_reads: jax.Array
    sa_open_cycles: jax.Array   # integral of (active subarrays - 1)+ over time (MASA static power)


def _bank_state0(nb: int, ns: int) -> dict:
    """Initial packed bank/subarray timing state (see state_layout.py).

    Three buffers instead of a ~30-leaf dict: the ``[nb, ns + 1, SA_F]``
    subarray plane (open_row = NEG, timing fields = 0; row ``ns`` is the
    bank-vector row: designated = open_sa = NEG, last_act = 0), the 4-deep
    ACT history, and the ``[SC_F]`` scalar/counter pack.
    """
    sa = (jnp.zeros((nb, ns + 1, L.SA_F), jnp.int32)
          .at[:, :, L.SA_OPEN_ROW].set(_NEG)       # also BK_DESIGNATED = NEG
          .at[:, ns, L.BK_OPEN_SA].set(_NEG))
    scalars = jnp.zeros((L.SC_F,), jnp.int32).at[L.SC_COL_LAST].set(-(10 ** 6))
    return dict(
        sa=sa,
        act_hist=jnp.zeros((4,), jnp.int32),  # last 4 ACT issue times, [0] oldest
        scalars=scalars,
    )


def _step_math(policy: int, t: DramTiming, refresh_mode: int,
               bk, act_hist, sc, req: dict,
               closed_row: bool = False, emit: bool = False):
    """The pure math phase of :func:`_timing_step`, on the gathered block.

    ``bk`` is the target bank's ``[ns + 1, SA_F]`` block (bank-vector row
    riding at index ``ns``), ``act_hist``/``sc`` the two scalar packs.
    Returns ``(new_bk, new_act_hist, new_sc, comp)`` — plus the command-log
    block when ``emit``. No gathers of the full plane and no scatters: the
    memory movement around this function is the caller's contract, which is
    exactly what lets three executors share ONE source of timing truth:

    * :func:`_timing_step` (the scan step) wraps it in the historical
      ``dynamic_slice`` / ``dynamic_update_slice`` pair;
    * the Pallas kernel (:mod:`repro.core.dram.pallas_step`) calls it on a
      block sliced from the kernel-resident state, per grid lane;
    * the lane-vectorized batched scan (``controller._simulate_stacked_lanes``)
      runs ``jax.vmap`` of it on the gathered blocks when refresh is on,
      and with refresh off cross-checks its row-wise reformulation
      (:func:`_step_math_lanes`) against ``jax.vmap`` of this.
    """
    b, s, w = req["bank"], req["subarray"], req["row"]
    is_wr, vis = req["is_write"], req["vis"]

    is_masa = policy == Policy.MASA
    ns_p1 = bk.shape[0]          # ns subarrays + the bank-vector row
    ns = ns_p1 - 1
    zero = jnp.int32(0)

    bv = bk[ns]                                          # bank-vector row
    designated, os_, last_act_bank = (bv[L.BK_DESIGNATED], bv[L.BK_OPEN_SA],
                                      bv[L.BK_LAST_ACT])

    # Own + other-subarray rows in one indexed gather. ``so`` is made
    # gather-safe independently of ``pre_other_needed`` (every consumer of
    # the other row is gated on it, so the row read when the gate is off is
    # irrelevant — but the index must stay in range).
    so = jnp.where(os_ != _NEG, os_, 0)
    pair = bk[jnp.stack([s, so])]                        # [2, SA_F]
    own, oth = pair[0], pair[1]
    orow = own[L.SA_OPEN_ROW]

    hit = orow == w
    act_needed = ~hit
    pre_own_needed = (orow != _NEG) & act_needed
    pre_other_needed = (jnp.bool_(not is_masa)) & (os_ != _NEG) & (os_ != s) & act_needed

    # ---- PRECHARGE timings (ready = after tRAS and write recovery)
    t_pre_other = jnp.maximum(vis, jnp.maximum(oth[L.SA_RAS_DONE],
                                               oth[L.SA_WRR_DONE]))
    t_pre_own = jnp.maximum(vis, jnp.maximum(own[L.SA_RAS_DONE],
                                             own[L.SA_WRR_DONE]))

    # ---- ACTIVATE timing
    t_act = jnp.maximum(vis, own[L.SA_PRE_DONE])                 # own subarray precharged
    t_act = jnp.maximum(t_act, last_act_bank + t.t_rrd_sa)
    t_act = jnp.maximum(t_act, act_hist[3] + t.t_rrd)            # global ACT-ACT
    t_act = jnp.maximum(t_act, act_hist[0] + t.t_faw)            # four-ACT window
    # own-subarray conflict: full PRE -> tRP -> ACT serialization (all policies)
    t_act = jnp.where(pre_own_needed, jnp.maximum(t_act, t_pre_own + t.t_rp), t_act)
    # cross-subarray coupling with the other subarray's PRE:
    if policy == Policy.BASELINE or policy == Policy.IDEAL:
        t_act = jnp.where(pre_other_needed, jnp.maximum(t_act, t_pre_other + t.t_rp), t_act)
    elif policy == Policy.SALP1:
        t_act = jnp.where(pre_other_needed, jnp.maximum(t_act, t_pre_other + 1), t_act)
    # SALP2 / MASA: ACT decoupled from the other subarray's PRE.

    # ---- column command
    t_col = jnp.where(hit, jnp.maximum(vis, own[L.SA_ACT_DONE]), t_act + t.t_rcd)
    if policy == Policy.SALP2:
        # global structures must be released: column waits for the other PRE's issue
        t_col = jnp.where(pre_other_needed, jnp.maximum(t_col, t_pre_other + 1), t_col)
    # MASA designation: SA_SEL needed when the bank's designated subarray changes
    # to serve a *hit* (a fresh ACT re-designates for free).
    sasel_needed = jnp.bool_(is_masa) & hit & (designated != s)
    t_col = jnp.where(sasel_needed, t_col + t.t_sa, t_col)
    # column bus: tCCD + write/read turnaround
    col_last = sc[L.SC_COL_LAST]
    col_last_wr = sc[L.SC_COL_LAST_WR] != 0
    t_col = jnp.maximum(t_col, col_last + t.t_ccd)
    t_col = jnp.where(~is_wr & col_last_wr,
                      jnp.maximum(t_col, sc[L.SC_WR_DATA_END] + t.t_wtr), t_col)
    t_col = jnp.where(is_wr & ~col_last_wr,
                      jnp.maximum(t_col, col_last + t.t_rtw), t_col)
    # data bus occupancy
    lat = jnp.where(is_wr, t.t_cwl, t.t_cl)
    t_col = jnp.maximum(t_col, sc[L.SC_DATA_BUS_FREE] - lat)
    data_start = t_col + lat
    data_end = data_start + t.t_bl

    comp = jnp.where(is_wr, t_col, data_end)

    # ---- state updates: [ns + 1] vectors + masks, scattered back in one go --
    # Unmasked broadcasts (refresh mode 1, closed-row pre_done ladder) may
    # touch the bank-vector row's lanes; that row is rebuilt wholesale below,
    # so nothing leaks.
    sidx = jnp.arange(ns_p1, dtype=jnp.int32)
    own_m = sidx == s
    oth_m = (sidx == so) & pre_other_needed
    own_pre_m = own_m & pre_own_needed
    act_m = own_m & act_needed

    # subarray-open-count integral (extra activated subarrays => static power)
    now = t_col  # integration checkpoint
    extra = jnp.maximum(sc[L.SC_OPEN_COUNT] - 1, 0)
    sa_open_cyc = sc[L.SC_SA_OPEN_CYC] + extra * jnp.maximum(
        now - sc[L.SC_LAST_OPEN_TIME], 0)
    last_open_time = jnp.maximum(now, sc[L.SC_LAST_OPEN_TIME])

    open_row = bk[:, L.SA_OPEN_ROW]
    act_done = bk[:, L.SA_ACT_DONE]
    ras_done = bk[:, L.SA_RAS_DONE]
    wrr_done = bk[:, L.SA_WRR_DONE]
    pre_done = bk[:, L.SA_PRE_DONE]

    # PRE other subarray (non-MASA path) + PRE own subarray
    open_row = jnp.where(oth_m | own_pre_m, _NEG, open_row)
    pre_done = jnp.where(oth_m, t_pre_other + t.t_rp, pre_done)
    pre_done = jnp.where(own_pre_m, t_pre_own + t.t_rp, pre_done)

    delta_open = (jnp.where(act_needed, 1, 0)
                  - jnp.where(pre_other_needed, 1, 0)
                  - jnp.where(pre_own_needed, 1, 0))
    open_count = sc[L.SC_OPEN_COUNT] + delta_open

    # ACT
    open_row = jnp.where(act_m, w, open_row)
    act_done = jnp.where(act_m, t_act + t.t_rcd, act_done)
    ras_done = jnp.where(act_m, t_act + t.t_ras, ras_done)
    wrr_done = jnp.where(act_m, 0, wrr_done)
    last_act_new = jnp.where(act_needed, t_act, last_act_bank)
    act_hist = jnp.where(
        act_needed, jnp.concatenate([act_hist[1:], t_act[None]]), act_hist)

    # write recovery bookkeeping (after the column command)
    wrr_done = jnp.where(own_m & is_wr,
                         jnp.maximum(wrr_done, data_end + t.t_wr), wrr_done)
    # read-to-precharge: fold tRTP into ras_done (both gate PRE)
    ras_done = jnp.where(own_m & ~is_wr,
                         jnp.maximum(ras_done, t_col + t.t_rtp), ras_done)

    open_sa_new = os_ if is_masa else s
    designated_new = s

    if refresh_mode:
        # refresh requires a precharged target: bank-granular refresh (REFab
        # mode 1, REFpb mode 3, DARP mode 4) closes every row in the bank;
        # subarray-granular refresh (DSARP mode 2, SARP mode 5) closes only
        # the refreshed subarray. The due-cycle bookkeeping lives in the
        # controller; this layer only applies the row closure it directs.
        ref_pending, ref_target = req["ref_pending"], req["ref_target"]
        if RefreshPolicy(refresh_mode).subarray_granular:
            open_row = jnp.where(ref_pending & (sidx == ref_target), _NEG,
                                 open_row)
        else:
            open_row = jnp.where(ref_pending, _NEG, open_row)

    if closed_row:
        # Auto-precharge after every access. The auto-PRE occupies the bank's
        # global structures exactly like an explicit PRE, so the policy ladder
        # applies: baseline serializes the NEXT ACT to the whole bank behind
        # tRP; SALP-1 overlaps all but the command slot; SALP-2/MASA are local.
        # The internal precharge obeys the SAME gates as an explicit PRE —
        # tRAS from the access's ACT, tRTP from a read, write recovery (tWR)
        # from a write's data end — mirroring the own-lane ras_done/wrr_done
        # updates above, so the checker holds PREA to the full PRE rule set
        # (the historical model let it fire up to 2 cycles inside tRAS and
        # ahead of tWR; docs/commands.md used to carry that as a caveat).
        ras_ready = jnp.where(act_needed, t_act + t.t_ras,
                              own[L.SA_RAS_DONE])
        rtp_ready = jnp.where(is_wr, zero, t_col + t.t_rtp)
        wr_ready = jnp.where(is_wr, data_end + t.t_wr,
                             jnp.where(act_needed, zero, own[L.SA_WRR_DONE]))
        auto_pre = jnp.maximum(jnp.maximum(data_end, ras_ready),
                               jnp.maximum(rtp_ready, wr_ready))
        open_row = jnp.where(own_m, _NEG, open_row)
        pre_done = jnp.where(own_m,
                             jnp.maximum(pre_done, auto_pre + t.t_rp), pre_done)
        if policy in (Policy.BASELINE, Policy.IDEAL):
            pre_done = jnp.maximum(pre_done, auto_pre + t.t_rp)
        elif policy == Policy.SALP1:
            pre_done = jnp.maximum(pre_done, auto_pre + 1)
            pre_done = jnp.where(own_m,
                                 jnp.maximum(pre_done, auto_pre + t.t_rp),
                                 pre_done)
        open_sa_new = _NEG
        open_count = open_count - jnp.where(act_needed, 1, 0)

    # ---- rebuild the block + scalar pack ------------------------------------
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    new_bk = jnp.stack([open_row, act_done, ras_done, wrr_done, pre_done],
                       axis=1)  # [ns + 1, SA_F]
    new_bv = jnp.stack([i32(designated_new), i32(open_sa_new), last_act_new,
                        zero, zero])
    new_bk = new_bk.at[ns].set(new_bv)  # static index: rebuilt bank-vector row
    new_sc = jnp.stack([
        t_col,                                               # SC_COL_LAST
        i32(is_wr),                                          # SC_COL_LAST_WR
        jnp.where(is_wr, data_end, sc[L.SC_WR_DATA_END]),    # SC_WR_DATA_END
        data_end,                                            # SC_DATA_BUS_FREE
        last_open_time,                                      # SC_LAST_OPEN_TIME
        open_count,                                          # SC_OPEN_COUNT
        sc[L.SC_C_ACT] + i32(act_needed),
        sc[L.SC_C_PRE] + i32(pre_other_needed) + i32(pre_own_needed),
        sc[L.SC_C_RD] + i32(~is_wr),
        sc[L.SC_C_WR] + i32(is_wr),
        sc[L.SC_C_SASEL] + i32(sasel_needed),
        sc[L.SC_C_HIT] + i32(hit),
        sc[L.SC_SUM_LAT] + jnp.where(is_wr, 0, comp - vis),
        sc[L.SC_C_READS] + i32(~is_wr),
        sa_open_cyc,                                         # SC_SA_OPEN_CYC
        jnp.maximum(sc[L.SC_MAX_COMP], comp),                # SC_MAX_COMP
    ])

    if not emit:
        return new_bk, act_hist, new_sc, comp

    # ---- packed command-log block (SimConfig.emit_commands) ----------------
    # One [CMD_F] row per command slot; a slot whose condition is off carries
    # OP_NOP. The issue cycles are exactly the t_* this step computed, so the
    # log IS the timing math — commands.decode flattens it and checker.py
    # re-verifies it against the declarative JEDEC rule table.
    def rec(cond, op, cycle, sa_i, row_i, aux=zero):
        return jnp.stack([jnp.where(cond, i32(op), jnp.int32(L.OP_NOP)),
                          i32(cycle), i32(b), i32(sa_i), i32(row_i), i32(aux)])

    slots = [
        # The other subarray's PRE may target a row the refresh machinery
        # already closed (open_row == NEG): the controller tracks BK_OPEN_SA,
        # not the closure, so the (harmless) PRE is still issued.
        rec(pre_other_needed, L.OP_PRE, t_pre_other, so, oth[L.SA_OPEN_ROW]),
        rec(pre_own_needed, L.OP_PRE, t_pre_own, s, orow),
        rec(act_needed, L.OP_ACT, t_act, s, w),
        # SA_SEL completes t_sa before the column command it redirects
        rec(sasel_needed, L.OP_SASEL, t_col - t.t_sa, s, _NEG),
        rec(jnp.bool_(True),
            jnp.where(is_wr, jnp.int32(L.OP_WR), jnp.int32(L.OP_RD)),
            t_col, s, w, aux=vis),
    ]
    if closed_row:
        slots.append(rec(jnp.bool_(True), L.OP_PREA, auto_pre, s, w))
    return new_bk, act_hist, new_sc, comp, jnp.stack(slots)


def _timing_step(policy: int, t: DramTiming, refresh_mode: int,
                 state: dict, req: dict,
                 closed_row: bool = False, emit: bool = False):
    """Serve one scheduled request against the bank state; return completion.

    ``req`` carries the request fields (``bank/subarray/row/is_write``), the
    controller-computed visibility cycle ``vis`` (gap / dependence / ROB /
    refresh blocking already folded in), and — when ``refresh_mode`` — the
    controller's refresh directive for the target bank (``ref_pending``,
    ``ref_target``: close the refreshed row(s) this step). ``refresh_mode``:
    0 = off; 1 = blocking all-bank refresh (baseline DRAM); 2 = DSARP-style
    subarray refresh (paper Sec. 6.1).

    Gather/scatter contract: exactly ONE ``dynamic_slice`` of the target
    bank's ``[ns + 1, SA_F]`` block in (the bank-vector row rides along),
    one ``[2, SA_F]`` indexed gather of the own/other subarray rows, and
    exactly ONE ``dynamic_update_slice`` out. Every conditional update is
    an unconditional write of ``jnp.where(cond, new, old)`` — never a
    ``where`` over a full array copy. The math between the two lives in
    :func:`_step_math`, shared verbatim with the Pallas kernel backend.

    ``emit`` (static, default off) additionally returns a packed
    ``[slots, CMD_F]`` int32 command-log block (state_layout ``CMD_*`` /
    ``OP_*``) — one slot per command the step may issue, ``OP_NOP`` marking
    the unused ones. The gate is a pure Python branch: the ``emit=False``
    path traces exactly the ops it always did (bit-identical results, no
    perf cost). Decode lives in :mod:`repro.core.dram.commands`.
    """
    b = req["bank"]
    sa = state["sa"]
    ns_p1 = sa.shape[1]
    zero = jnp.int32(0)
    bk = jax.lax.dynamic_slice(sa, (b, zero, zero),
                               (1, ns_p1, L.SA_F))[0]    # [ns + 1, SA_F]
    out = _step_math(policy, t, refresh_mode, bk, state["act_hist"],
                     state["scalars"], req, closed_row=closed_row, emit=emit)
    new_bk, act_hist, new_sc, comp = out[:4]
    new = dict(state)
    new["sa"] = jax.lax.dynamic_update_slice(sa, new_bk[None], (b, zero, zero))
    new["act_hist"], new["scalars"] = act_hist, new_sc
    if not emit:
        return new, comp
    return new, comp, out[4]


def _step_math_lanes(policy: int, t: DramTiming, own, oth, bv, act_hist, col,
                     req: dict):
    """Row-wise, lane-batched reformulation of :func:`_step_math`.

    The lanes scan's step with refresh off (open-row policy, no command
    emission; with refresh on a pending refresh closes more rows, and the
    lanes scan runs :func:`_step_math` on the whole gathered block).
    There one step can change exactly three rows of the
    packed plane — the request's own subarray ``s``, the previously open
    subarray ``so`` (non-MASA precharge coupling), and the bank-vector row —
    so instead of masked ``[ns + 1]`` column vectors over the whole gathered
    block this variant computes just those rows, batched over ``B``
    independent lanes (traces): ``own``/``oth``/``bv`` are ``[B, SA_F]``
    gathered rows, ``act_hist`` is ``[B, 4]``, and every ``req`` field is a
    ``[B]`` vector.

    Only the four channel scalars the timing math actually *reads* are
    carried (``col``: last column issue / was-it-a-write / write-data-end /
    data-bus-free, each ``[B]``); every SimResult counter is instead
    reconstructed after the scan from the per-step ``flags`` this returns
    (see ``controller._simulate_stacked_lanes``) — O(N·B) vectorized work
    once, instead of ~10 tiny accumulator ops inside every step.

    Same int32 op sequence as :func:`_step_math` restricted to the three
    rows, so the results are bit-identical to ``jax.vmap`` of the reference
    — the stacked-vs-single parity suites in tests/test_packed_state.py pin
    that equivalence on every policy/geometry combo.

    Returns ``(own_new, oth_new, bv_new, act_hist_new, col_new, comp,
    flags)``; ``oth_new`` is ``None`` under MASA (no cross-subarray
    precharge — the caller skips the other row's gather and scatter
    entirely).
    """
    s, w = req["subarray"], req["row"]
    is_wr, vis = req["is_write"], req["vis"]
    is_masa = policy == Policy.MASA

    designated = bv[:, L.BK_DESIGNATED]
    os_ = bv[:, L.BK_OPEN_SA]
    last_act_bank = bv[:, L.BK_LAST_ACT]
    orow = own[:, L.SA_OPEN_ROW]

    hit = orow == w
    act_needed = ~hit
    pre_own_needed = (orow != _NEG) & act_needed
    if is_masa:
        pre_other_needed = jnp.zeros_like(hit)
    else:
        pre_other_needed = (os_ != _NEG) & (os_ != s) & act_needed
        t_pre_other = jnp.maximum(vis, jnp.maximum(oth[:, L.SA_RAS_DONE],
                                                   oth[:, L.SA_WRR_DONE]))
    t_pre_own = jnp.maximum(vis, jnp.maximum(own[:, L.SA_RAS_DONE],
                                             own[:, L.SA_WRR_DONE]))

    # ---- ACTIVATE timing (same max-chain as the reference)
    t_act = jnp.maximum(vis, own[:, L.SA_PRE_DONE])
    t_act = jnp.maximum(t_act, last_act_bank + t.t_rrd_sa)
    t_act = jnp.maximum(t_act, act_hist[:, 3] + t.t_rrd)
    t_act = jnp.maximum(t_act, act_hist[:, 0] + t.t_faw)
    t_act = jnp.where(pre_own_needed, jnp.maximum(t_act, t_pre_own + t.t_rp),
                      t_act)
    if policy == Policy.BASELINE or policy == Policy.IDEAL:
        t_act = jnp.where(pre_other_needed,
                          jnp.maximum(t_act, t_pre_other + t.t_rp), t_act)
    elif policy == Policy.SALP1:
        t_act = jnp.where(pre_other_needed,
                          jnp.maximum(t_act, t_pre_other + 1), t_act)

    # ---- column command
    t_col = jnp.where(hit, jnp.maximum(vis, own[:, L.SA_ACT_DONE]),
                      t_act + t.t_rcd)
    if policy == Policy.SALP2:
        t_col = jnp.where(pre_other_needed,
                          jnp.maximum(t_col, t_pre_other + 1), t_col)
    sasel_needed = jnp.bool_(is_masa) & hit & (designated != s)
    t_col = jnp.where(sasel_needed, t_col + t.t_sa, t_col)
    col_last, col_last_wr = col["col_last"], col["col_last_wr"]
    t_col = jnp.maximum(t_col, col_last + t.t_ccd)
    t_col = jnp.where(~is_wr & col_last_wr,
                      jnp.maximum(t_col, col["wr_data_end"] + t.t_wtr), t_col)
    t_col = jnp.where(is_wr & ~col_last_wr,
                      jnp.maximum(t_col, col_last + t.t_rtw), t_col)
    lat = jnp.where(is_wr, t.t_cwl, t.t_cl)
    t_col = jnp.maximum(t_col, col["bus_free"] - lat)
    data_start = t_col + lat
    data_end = data_start + t.t_bl
    comp = jnp.where(is_wr, t_col, data_end)

    col_new = dict(col_last=t_col, col_last_wr=is_wr,
                   wr_data_end=jnp.where(is_wr, data_end,
                                         col["wr_data_end"]),
                   bus_free=data_end)

    # ---- the three changed rows -------------------------------------------
    # Other subarray (non-MASA): PRE closes it. Identity when the gate is
    # off; when ``so == s`` (gate necessarily off: pre_other requires
    # os_ != s) the own row is scattered after this one and wins.
    if is_masa:
        oth_new = None
    else:
        oth_new = jnp.stack([
            jnp.where(pre_other_needed, _NEG, oth[:, L.SA_OPEN_ROW]),
            oth[:, L.SA_ACT_DONE],
            oth[:, L.SA_RAS_DONE],
            oth[:, L.SA_WRR_DONE],
            jnp.where(pre_other_needed, t_pre_other + t.t_rp,
                      oth[:, L.SA_PRE_DONE]),
        ], axis=1)

    # Own subarray: the reference's own_pre_m sets open_row = NEG, but
    # pre_own_needed implies act_needed, so the ACT's ``w`` always wins.
    own_open = jnp.where(act_needed, w, orow)
    own_act = jnp.where(act_needed, t_act + t.t_rcd, own[:, L.SA_ACT_DONE])
    own_ras = jnp.where(act_needed, t_act + t.t_ras, own[:, L.SA_RAS_DONE])
    own_ras = jnp.where(~is_wr, jnp.maximum(own_ras, t_col + t.t_rtp), own_ras)
    own_wrr = jnp.where(act_needed, 0, own[:, L.SA_WRR_DONE])
    own_wrr = jnp.where(is_wr, jnp.maximum(own_wrr, data_end + t.t_wr),
                        own_wrr)
    own_pre = jnp.where(pre_own_needed, t_pre_own + t.t_rp,
                        own[:, L.SA_PRE_DONE])
    own_new = jnp.stack([own_open, own_act, own_ras, own_wrr, own_pre], axis=1)

    # Bank-vector row (rebuilt wholesale, like the reference)
    open_sa_new = os_ if is_masa else s
    last_act_new = jnp.where(act_needed, t_act, last_act_bank)
    zero_b = jnp.zeros_like(s)
    bv_new = jnp.stack([s, open_sa_new, last_act_new, zero_b, zero_b], axis=1)

    act_hist_new = jnp.where(
        act_needed[:, None],
        jnp.concatenate([act_hist[:, 1:], t_act[:, None]], axis=1), act_hist)

    # per-step facts the post-scan counter reconstruction needs (raw, no
    # int32 conversions here — the scan just stacks them). Flags that are
    # constant-off for the policy (sasel without MASA, pre_oth under MASA)
    # are omitted rather than stacked as all-zero [N, B] planes.
    flags = dict(t_col=t_col, hit=hit, pre_own=pre_own_needed)
    if is_masa:
        flags["sasel"] = sasel_needed
    else:
        flags["pre_oth"] = pre_other_needed
    return own_new, oth_new, bv_new, act_hist_new, col_new, comp, flags


def _controller_args(policy: Policy, config: SimConfig):
    """Resolve (effective policy, geometry, static kwargs) for the controller."""
    nb, ns = config.geometry_for(policy)
    eff = Policy.BASELINE if policy == Policy.IDEAL else policy
    return int(eff), int(Scheduler(config.scheduler)), nb, ns


def result_from_state(n_requests, scalars, vis_prev) -> SimResult:
    """Unpack the packed scalar carry into the public SimResult counters."""
    return SimResult(
        total_cycles=jnp.maximum(scalars[L.SC_MAX_COMP], jnp.max(vis_prev)),
        n_requests=jnp.int32(n_requests),
        n_act=scalars[L.SC_C_ACT], n_pre=scalars[L.SC_C_PRE],
        n_rd=scalars[L.SC_C_RD], n_wr=scalars[L.SC_C_WR],
        n_sasel=scalars[L.SC_C_SASEL], n_hit=scalars[L.SC_C_HIT],
        sum_latency=scalars[L.SC_SUM_LAT], n_reads=scalars[L.SC_C_READS],
        sa_open_cycles=scalars[L.SC_SA_OPEN_CYC],
    )


def simulate(trace: Trace, policy: Policy, config: SimConfig = SimConfig()) -> SimResult:
    """Simulate one trace under one policy (a 1-core controller instance)."""
    from repro.core.dram import controller  # deferred: controller builds on this layer

    if config.emit_commands:
        raise ValueError(
            "SimConfig.emit_commands is consumed by the command-export entry "
            "points — use repro.core.dram.commands.simulate_commands "
            "(simulate() would silently drop the log)")
    controller.validate_mlp_window(trace.mlp_window)
    eff, sched, nb, ns = _controller_args(policy, config)
    tr = to_ideal(trace, config.n_banks, config.n_subarrays) if policy == Policy.IDEAL else trace
    if config.backend != "scan":
        # fused Pallas lane kernel, B = 1 (docs/kernels.md); interpret=True
        # executes the kernel's op graph on CPU — the CI parity path
        from repro.core.dram import pallas_step
        res, _ = pallas_step._simulate_lanes_pallas(
            eff, nb, ns, config.timing, config.refresh_mode,
            jnp.asarray(tr.bank)[None], jnp.asarray(tr.subarray)[None],
            jnp.asarray(tr.row)[None], jnp.asarray(tr.is_write)[None],
            jnp.asarray(tr.gap)[None], jnp.asarray(tr.dep)[None],
            jnp.asarray([trace.mlp_window], jnp.int32),
            closed_row=config.row_policy == "closed",
            interpret=config.backend == "pallas-interpret")
        return jax.tree_util.tree_map(lambda x: x[0], res)
    res, _ = controller._simulate_controller(
        eff, sched, nb, ns, config.timing, config.refresh_mode,
        jnp.asarray(tr.bank)[None], jnp.asarray(tr.subarray)[None],
        jnp.asarray(tr.row)[None], jnp.asarray(tr.is_write)[None],
        jnp.asarray(tr.gap)[None], jnp.asarray(tr.dep)[None],
        jnp.asarray([trace.mlp_window], jnp.int32),
        jnp.zeros((1,), jnp.int32),
        closed_row=config.row_policy == "closed")
    return res


def runs_lanes(config: SimConfig) -> bool:
    """Whether :func:`simulate_stacked` serves a batch under ``config`` with
    the lane-batched scan (``controller._simulate_stacked_lanes``): the scan
    backend, open rows and no command emission, under any refresh policy.
    Closed-row and command-export batches take ``vmap`` of the per-trace
    controller instead."""
    return (config.backend == "scan" and config.row_policy == "open"
            and not config.emit_commands)


def simulate_stacked(stacked: dict, policy: Policy,
                     config: SimConfig = SimConfig()) -> SimResult:
    """Batched entry point: simulate pre-stacked [B, N] arrays in one program.

    ``stacked`` is the dict produced by :func:`repro.core.dram.trace.stack_traces`
    (fields ``bank/subarray/row/is_write/gap/dep`` of shape [B, N] and
    ``mlp_window`` of shape [B]). All B rows share one compiled program — this
    is the primitive the experiment-sweep subsystem buckets cells onto. Each
    row is one single-core controller instance: the lane-batched scan where
    :func:`runs_lanes` says so, else ``vmap`` of the per-trace controller.
    """
    from repro.core.dram import controller

    controller.validate_mlp_window(stacked["mlp_window"])
    eff, sched, nb, ns = _controller_args(policy, config)
    bank = jnp.asarray(stacked["bank"])
    subarray = jnp.asarray(stacked["subarray"])
    if policy == Policy.IDEAL:
        # to_ideal() on stacked arrays: every subarray becomes a real bank
        bank = bank * config.n_subarrays + subarray
        subarray = jnp.zeros_like(subarray)
    if config.backend != "scan":
        # fused Pallas lane kernel: the batch dimension is the kernel grid
        # axis, no outer vmap (docs/kernels.md). Refuses emit_commands.
        from repro.core.dram import pallas_step
        pallas_step.check_no_emit(config)
        res, _ = pallas_step._simulate_lanes_pallas(
            eff, nb, ns, config.timing, config.refresh_mode,
            bank, subarray,
            jnp.asarray(stacked["row"]), jnp.asarray(stacked["is_write"]),
            jnp.asarray(stacked["gap"]), jnp.asarray(stacked["dep"]),
            jnp.asarray(stacked["mlp_window"], jnp.int32),
            closed_row=config.row_policy == "closed",
            interpret=config.backend == "pallas-interpret")
        return res
    if runs_lanes(config):
        # lane-vectorized single-scan fast path, refresh included
        # (bit-identical; see controller._simulate_stacked_lanes). A
        # batch-uniform mlp_window is promoted to a static scalar so the
        # completion ring's ROB read becomes a contiguous slice.
        import numpy as np
        mw = np.asarray(stacked["mlp_window"])
        mlp_static = int(mw.flat[0]) if (mw == mw.flat[0]).all() else None
        return controller._simulate_stacked_lanes(
            eff, nb, ns, config.timing,
            bank, subarray,
            jnp.asarray(stacked["row"]), jnp.asarray(stacked["is_write"]),
            jnp.asarray(stacked["gap"]), jnp.asarray(stacked["dep"]),
            jnp.asarray(stacked["mlp_window"], jnp.int32),
            mlp_static=mlp_static, refresh_mode=config.refresh_mode)
    fn = functools.partial(controller._simulate_controller, eff, sched, nb, ns,
                           config.timing, config.refresh_mode,
                           closed_row=config.row_policy == "closed")

    def one(b, s, r, w, g, d, m):
        res, _ = fn(b[None], s[None], r[None], w[None], g[None], d[None],
                    m[None].astype(jnp.int32), jnp.zeros((1,), jnp.int32))
        return res

    return jax.vmap(one)(
        bank, subarray,
        jnp.asarray(stacked["row"]), jnp.asarray(stacked["is_write"]),
        jnp.asarray(stacked["gap"]), jnp.asarray(stacked["dep"]),
        jnp.asarray(stacked["mlp_window"]))


def simulate_batch(traces: list[Trace], policy: Policy,
                   config: SimConfig = SimConfig()) -> SimResult:
    """vmap the simulator over a stack of equal-length traces."""
    return simulate_stacked(stack_traces(traces), policy, config)
