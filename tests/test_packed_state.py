"""Packed-state engine parity: the state-layout rewrite must be bit-exact.

Two lines of defense on top of the literal pins in test_dram_engine /
test_controller:

* **Golden fixture** (``tests/data/golden_packed_state.json``): counters for
  198 cells — seeded random small traces x policy x refresh_mode x
  row_policy, plus 2-core mixes x scheduler — captured from the
  pre-packed-state engine (commit 37b6d6b). Any drift is a timing-semantics
  change, not noise.
* **Hypothesis fuzz**: ``simulate_stacked`` (the vmapped primitive the sweep
  runner buckets onto) must equal a per-trace ``simulate`` loop bit-for-bit
  across policy x refresh x row-policy combos on random traces.
"""
import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from repro.core.dram import (ROW_SPACE_STRIDE, Policy, Scheduler, SimConfig,
                             generate_trace, simulate, workload)
from repro.core.dram.engine import SimResult, simulate_stacked
from repro.core.dram.multicore import (simulate_multicore,
                                       simulate_multicore_batch)
from repro.core.dram.trace import Trace, WorkloadProfile, stack_traces

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "data",
                           "golden_packed_state.json")

#: Execution backends under test. "pallas-interpret" runs the fused Pallas
#: kernels (repro.core.dram.pallas_step) with interpret=True — the CPU/CI
#: leg of the bit-parity contract; "scan" is the packed lax.scan reference.
#: The compiled "pallas" backend is refused at SimConfig construction (the
#: TPU compiler rejects the kernel; see engine.PALLAS_COMPILE_ERROR).
BACKENDS = ("scan", "pallas-interpret")

#: Refresh-engaged timing for the ladder's fixture cells (see CONFIGS).
REF_TIMING = dataclasses.replace(
    SimConfig().timing, t_refi=520, t_rfc=80, t_rfc_pb=32, ref_postpone_max=2)

CONFIGS = {
    "default": dict(),
    "refresh": dict(refresh=True),
    "dsarp": dict(refresh=True, dsarp=True),
    "closed": dict(row_policy="closed"),
    "closed_refresh": dict(refresh=True, row_policy="closed"),
    # refresh-policy ladder (PR 5). "all_bank"/"dsarp_policy" cells carry
    # counters COPIED from the "refresh"/"dsarp" cells when the fixture was
    # extended — the golden file itself pins the deprecation-shim
    # equivalence bit-for-bit. per_bank/darp/sarp pin the new modes under
    # REF_TIMING: the fixture traces run ~2-3k cycles, far short of the
    # default 4160-cycle tREFI, so the default timing would pin nothing —
    # the shrunk tREFI/window makes every mechanism (deadlines, idle drain,
    # write shadow, forced overflow) actually fire inside the trace.
    "all_bank": dict(refresh_policy="all_bank"),
    "dsarp_policy": dict(refresh_policy="dsarp"),
    "per_bank": dict(refresh_policy="per_bank", timing=REF_TIMING),
    "darp": dict(refresh_policy="darp", timing=REF_TIMING),
    "sarp": dict(refresh_policy="sarp", timing=REF_TIMING),
}


def counters(res: SimResult) -> dict:
    return {f.name: int(np.asarray(getattr(res, f.name)))
            for f in dataclasses.fields(SimResult)}


def random_trace(seed: int, n: int = 120, nb: int = 8, ns: int = 8,
                 mlp: int | None = None) -> Trace:
    """Seeded random trace — MUST stay in lockstep with the fixture's
    generator (tools that regenerate the golden file use this recipe)."""
    rng = np.random.default_rng(seed)
    banks = rng.integers(0, nb, n)
    rows = rng.integers(0, 64, n)
    loc = rng.random()
    for i in range(1, n):
        if rng.random() < loc:
            banks[i], rows[i] = banks[i - 1], rows[i - 1]
    sas = (rows * 2654435761 >> 11) % ns
    wr = rng.random(n) < rng.random() * 0.8
    gaps = rng.integers(0, 30, n)
    deps = (rng.random(n) < 0.4) & ~wr
    deps[0] = False
    return Trace(bank=banks.astype(np.int32), subarray=sas.astype(np.int32),
                 row=rows.astype(np.int32), is_write=wr,
                 gap=gaps.astype(np.int32), dep=deps,
                 mlp_window=mlp if mlp is not None else int(rng.integers(1, 16)),
                 profile=WorkloadProfile("g", 10, .3, 4, 2, 4, .2, .3))


@pytest.fixture(scope="module")
def golden() -> dict:
    with open(GOLDEN_PATH) as f:
        return json.load(f)


@pytest.mark.parametrize("backend", BACKENDS)
class TestGoldenParity:
    """Bit-exact counters vs the pre-packed-state engine, 198 cells.

    Parametrized over the backend axis: the Pallas kernels must reproduce
    the SAME golden counters on every cell — refresh ladder, closed-row,
    schedulers and all (the ISSUE's bit-parity acceptance criterion).
    """

    def test_single_core_cells(self, golden, backend):
        mismatches = []
        for cell in golden["single"]:
            tr = random_trace(cell["seed"])
            got = counters(simulate(tr, Policy[cell["policy"]],
                                    SimConfig(backend=backend,
                                              **CONFIGS[cell["config"]])))
            if got != cell["counters"]:
                mismatches.append((cell["seed"], cell["config"],
                                   cell["policy"], got, cell["counters"]))
        assert not mismatches, mismatches[:3]

    def test_multicore_cells(self, golden, backend):
        """Every multicore cell through ``simulate_multicore``; on the scan
        backend also both seeds of each (config, scheduler, policy) as one
        ``simulate_multicore_batch`` of two mixes, on the lane-major scan's
        lanes."""
        mismatches = []
        groups: dict[tuple, list] = {}
        for cell in golden["multicore"]:
            mix = [generate_trace(workload(m), 150, seed=cell["seed"],
                                  row_space_offset=ROW_SPACE_STRIDE * i)
                   for i, m in enumerate(("mcf", "lbm"))]
            cfg = SimConfig(scheduler=Scheduler[cell["scheduler"]],
                            backend=backend, **CONFIGS[cell["config"]])
            r = simulate_multicore(mix, Policy[cell["policy"]], cfg)
            got = counters(r.shared)
            cc = [int(x) for x in r.core_cycles]
            if got != cell["counters"] or cc != cell["core_cycles"]:
                mismatches.append((cell["seed"], cell["config"],
                                   cell["scheduler"], cell["policy"]))
            groups.setdefault((cell["config"], cell["scheduler"],
                               cell["policy"], cfg), []).append((cell, mix))
        if backend == "scan":
            for (*combo, cfg), members in groups.items():
                batch = simulate_multicore_batch(
                    [mix for _, mix in members], Policy[combo[2]], cfg)
                for (cell, _), r in zip(members, batch):
                    if (counters(r.shared) != cell["counters"]
                            or [int(x) for x in r.core_cycles]
                            != cell["core_cycles"]):
                        mismatches.append(("batch", cell["seed"], *combo))
        assert not mismatches, mismatches


@pytest.fixture
def release_programs():
    """Drop every compiled program once the test ends. Each one holds
    hundreds of memory mappings, and a process that runs this whole module
    otherwise comes near the kernel's per-process limit on them."""
    yield
    jax.clear_caches()


@pytest.mark.parametrize("config_name", sorted(CONFIGS))
def test_golden_single_core_cells_stacked(golden, config_name,
                                          release_programs):
    """Every golden single-core cell of one configuration through
    ``simulate_stacked``, one batch per policy, against the pinned counters.

    ``test_single_core_cells`` runs them through ``simulate``, the per-trace
    controller; this pins the batched path the sweeps run: the lanes scan
    for open rows (refresh ladder, IDEAL remap, per-lane ``mlp_window`` on
    the ring's dynamic path) and ``vmap`` of the controller for closed rows.
    """
    by_policy: dict[str, list] = {}
    for cell in golden["single"]:
        if cell["config"] == config_name:
            by_policy.setdefault(cell["policy"], []).append(cell)
    assert set(by_policy) == {p.name for p in Policy}
    cfg = SimConfig(**CONFIGS[config_name])
    mismatches = []
    for policy, cells in by_policy.items():
        res = simulate_stacked(
            stack_traces([random_trace(c["seed"]) for c in cells]),
            Policy[policy], cfg)
        for i, cell in enumerate(cells):
            got = {f.name: int(np.asarray(getattr(res, f.name))[i])
                   for f in dataclasses.fields(SimResult)}
            if got != cell["counters"]:
                mismatches.append((cell["seed"], policy, got,
                                   cell["counters"]))
    assert not mismatches, mismatches[:3]


class TestFixtureShape:
    """Backend-independent fixture/meta checks."""

    def test_fixture_covers_all_axes(self, golden):
        """The fixture really spans policy x refresh x row-policy x sched."""
        single = {(c["config"], c["policy"]) for c in golden["single"]}
        assert single == {(c, p.name) for c in CONFIGS for p in Policy}
        multi = {(c["config"], c["scheduler"], c["policy"])
                 for c in golden["multicore"]}
        # darp gets the full scheduler product (it feeds the schedulers'
        # refresh-urgency tier); per_bank/sarp pin the C-core directive
        # path under FR-FCFS only to bound compile count.
        full = {(c, s.name, p.name)
                for c in ("default", "refresh", "dsarp", "darp")
                for s in Scheduler
                for p in (Policy.BASELINE, Policy.MASA)}
        frfcfs_only = {(c, "FRFCFS", p.name)
                       for c in ("per_bank", "sarp")
                       for p in (Policy.BASELINE, Policy.MASA)}
        assert multi == full | frfcfs_only

    def test_shim_configs_equal_policy_configs(self):
        """The deprecated pair and the refresh_policy spelling are the SAME
        config — field-identical, so cache keys and buckets cannot differ."""
        assert (dataclasses.astuple(SimConfig(**CONFIGS["refresh"]))
                == dataclasses.astuple(SimConfig(**CONFIGS["all_bank"])))
        assert (dataclasses.astuple(SimConfig(**CONFIGS["dsarp"]))
                == dataclasses.astuple(SimConfig(**CONFIGS["dsarp_policy"])))


# --------------------------------------------------------------------------
# Stacked/batched path == per-trace loop, bit-for-bit.
# --------------------------------------------------------------------------

#: The refresh ladder with its deadlines inside the stacked-parity traces:
#: every mode under REF_TIMING (CONFIGS' "all_bank" / "dsarp" cells keep the
#: default tREFI the golden fixture was captured with).
LADDER = {
    "all_bank_ref": dict(refresh_policy="all_bank", timing=REF_TIMING),
    "dsarp_ref": dict(refresh_policy="dsarp", timing=REF_TIMING),
    "per_bank": CONFIGS["per_bank"], "darp": CONFIGS["darp"],
    "sarp": CONFIGS["sarp"],
}
PARITY_CONFIGS = {**CONFIGS, **LADDER}

#: Length of the ladder cases' traces (:func:`ladder_trace`).
LADDER_N = 512

# Bounded combo list so the parity tests reuse a handful of compiled
# programs instead of compiling per example (trace length is fixed too).
# Every refresh mode runs under BASELINE, SALP2, MASA and IDEAL: open-row
# refresh batches take the lane-batched scan (engine.runs_lanes).
COMBOS = [
    (Policy.BASELINE, "default"), (Policy.SALP2, "default"),
    (Policy.MASA, "default"), (Policy.IDEAL, "default"),
    (Policy.MASA, "refresh"), (Policy.MASA, "dsarp"),
    (Policy.BASELINE, "refresh"), (Policy.MASA, "closed"),
    (Policy.MASA, "per_bank"), (Policy.MASA, "darp"),
    (Policy.SALP2, "sarp"),
]
COMBOS += [(p, c) for c in LADDER
           for p in (Policy.BASELINE, Policy.SALP2, Policy.MASA, Policy.IDEAL)
           if (p, c) not in COMBOS]

#: Per-lane windows of the cases whose lanes differ in ``mlp_window``: the
#: lanes scan then reads the completion ring with a cross-lane gather
#: instead of a static slice.
MIXED_MLP = (3, 7, 12)

#: (policy, config, mlp) cases of the deterministic parity test: every combo
#: with one shared window, and one case per refresh mode with MIXED_MLP.
CASES = ([(p, c, 4) for p, c in COMBOS]
         + [(Policy.MASA, c, MIXED_MLP) for c in LADDER])


def ladder_trace(seed: int, n: int = LADDER_N,
                 mlp: int | None = None) -> Trace:
    """:func:`random_trace` with a hot middle half under REF_TIMING.

    The middle half goes back to back (gap 0, no dependences) to two rows
    of one subarray in turn, so every request is a row conflict: its first
    five eighths are reads, and DARP's debt outgrows the postpone window
    and forces bursts; its writes then carry write-shadow refreshes. The
    random quarters around it leave idle gaps that the idle drain fills.
    :func:`test_ladder_traces_fire_every_chain` checks that all three
    chains fire.
    """
    tr = random_trace(seed, n=n, mlp=mlp)
    a, b, c = n // 4, 5 * n // 8, 3 * n // 4
    bank, sa, row = tr.bank.copy(), tr.subarray.copy(), tr.row.copy()
    gap, wr, dep = tr.gap.copy(), tr.is_write.copy(), tr.dep.copy()
    bank[a:c], sa[a:c], gap[a:c], dep[a:c] = bank[a], sa[a], 0, False
    row[a:c] = row[a] + np.arange(c - a) % 2
    wr[a:b] = False
    return dataclasses.replace(tr, bank=bank, subarray=sa, row=row, gap=gap,
                               is_write=wr, dep=dep)


def _parity_traces(seed: int, cfg_name: str, mlp) -> list[Trace]:
    """Three equal-length traces: one compiled program per case."""
    if cfg_name in LADDER:
        mlps = mlp if isinstance(mlp, tuple) else (mlp,) * 3
        return [ladder_trace(seed + i, mlp=m) for i, m in enumerate(mlps)]
    return [random_trace(seed + i, n=64, mlp=mlp) for i in range(3)]


def _assert_stacked_matches(seed: int, policy: Policy, cfg_name: str,
                            mlp, backend: str = "scan") -> None:
    cfg = SimConfig(backend=backend, **PARITY_CONFIGS[cfg_name])
    ref_cfg = SimConfig(**PARITY_CONFIGS[cfg_name])   # per-trace reference
    traces = _parity_traces(seed, cfg_name, mlp)
    stacked = simulate_stacked(stack_traces(traces), policy, cfg)
    for i, tr in enumerate(traces):
        ref = counters(simulate(tr, policy, ref_cfg))
        got = {f.name: int(np.asarray(getattr(stacked, f.name))[i])
               for f in dataclasses.fields(SimResult)}
        assert got == ref, (policy, cfg_name, backend, i)


def _case_id(case) -> str:
    policy, cfg_name, mlp = case
    tail = "" if mlp == 4 else "-mlp" + ".".join(map(str, mlp))
    return f"{policy.name}-{cfg_name}{tail}"


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("combo", CASES, ids=[_case_id(c) for c in CASES])
def test_stacked_equals_per_trace_simulate(combo, backend):
    """Deterministic stacked-vs-loop parity (runs without hypothesis)."""
    policy, cfg_name, mlp = combo
    _assert_stacked_matches(seed=1000 + CASES.index(combo), policy=policy,
                            cfg_name=cfg_name, mlp=mlp, backend=backend)


@pytest.mark.parametrize("combo", [c for c in CASES if c[1] == "darp"],
                         ids=_case_id)
def test_ladder_traces_fire_every_chain(combo):
    """The DARP parity traces exercise all of DARP: decoding the first
    lane's per-trace command export finds an idle-drain, a forced and a
    write-shadow refresh chain."""
    import jax.numpy as jnp

    from repro.core.dram import controller, state_layout as L
    from repro.core.dram.engine import _controller_args
    from repro.core.dram.trace import to_ideal

    policy, cfg_name, mlp = combo
    cfg = SimConfig(emit_commands=True, **PARITY_CONFIGS[cfg_name])
    tr = _parity_traces(1000 + CASES.index(combo), cfg_name, mlp)[0]
    if policy == Policy.IDEAL:
        tr = to_ideal(tr, cfg.n_banks, cfg.n_subarrays)
    eff, sched, nb, ns = _controller_args(policy, cfg)
    _, _, ys = controller._simulate_controller(
        eff, sched, nb, ns, cfg.timing, cfg.refresh_mode,
        *(jnp.asarray(getattr(tr, f))[None]
          for f in ("bank", "subarray", "row", "is_write", "gap", "dep")),
        jnp.asarray([tr.mlp_window], jnp.int32), jnp.zeros((1,), jnp.int32),
        emit_commands=True)
    # DARP's three REF slots close each step's log: idle drain, forced
    # overflow, write shadow (controller._refresh_fns' ref_cmds)
    fired = (np.asarray(ys["cmds"])[:, -3:, L.CMD_OP] == L.OP_REF).sum(0)
    assert (fired > 0).all(), dict(zip(("idle", "forced", "shadow"),
                                       fired.tolist()))


ROUTES = [(dict(), True)]
ROUTES += [(dict(refresh_policy=rp), True)
           for rp in ("all_bank", "dsarp", "per_bank", "darp", "sarp")]
ROUTES += [(dict(refresh_policy="darp", row_policy="closed"), False),
           (dict(row_policy="closed"), False),
           (dict(refresh_policy="darp", emit_commands=True), False),
           (dict(emit_commands=True), False)]


@pytest.mark.parametrize(
    "cfg_kw, lanes", ROUTES,
    ids=["-".join(f"{k}={v}" for k, v in kw.items()) or "default"
         for kw, _ in ROUTES])
def test_runs_lanes_routes_batches(monkeypatch, cfg_kw, lanes):
    """``engine.runs_lanes`` decides the path: open-row batches without
    command export take the lane-batched scan under every refresh policy;
    closed-row and command-export batches take vmap of the per-trace
    controller."""
    from repro.core.dram import controller, engine

    cfg = SimConfig(**cfg_kw)
    assert engine.runs_lanes(cfg) is lanes
    taken = []
    for name in ("_simulate_stacked_lanes", "_simulate_controller"):
        real = getattr(controller, name)

        def spy(*a, _real=real, _name=name, **k):
            taken.append(_name)
            return _real(*a, **k)

        monkeypatch.setattr(controller, name, spy)
    simulate_stacked(stack_traces([random_trace(3, n=16),
                                   random_trace(4, n=16)]), Policy.MASA, cfg)
    assert set(taken) == {"_simulate_stacked_lanes" if lanes
                          else "_simulate_controller"}


def test_pallas_refuses_emit_commands():
    """emit_commands x pallas must raise, never silently drop the log; the
    compiled backend is refused before that, at construction, with the TPU
    compiler's reason."""
    from repro.core.dram.commands import simulate_commands
    from repro.core.dram.trace import stack_traces as _stack

    for kw in ({}, {"emit_commands": True}):
        with pytest.raises(ValueError, match="Unimplemented primitive in "
                                             "Pallas TPU lowering"):
            SimConfig(backend="pallas", **kw)
    with pytest.raises(ValueError, match="block shape"):
        dataclasses.replace(SimConfig(), backend="pallas")

    tr = random_trace(5, n=16)
    cfg = SimConfig(backend="pallas-interpret")
    with pytest.raises(ValueError, match="emit_commands"):
        simulate_commands(tr, Policy.MASA, cfg)
    with pytest.raises(ValueError, match="emit_commands"):
        simulate_stacked(_stack([tr]), Policy.MASA,
                         dataclasses.replace(cfg, emit_commands=True))


def test_scan_commands_match_pallas_counters():
    """Cross-check: the scan path's emitted-command run must agree with the
    kernel path's counters on the same cell (the refusal above plus this
    equivalence is the 'refuse or match' contract for command streams)."""
    from repro.core.dram.commands import simulate_commands

    tr = random_trace(11)
    for cfg_name in ("default", "per_bank"):
        res_cmd, _ = simulate_commands(tr, Policy.MASA,
                                       SimConfig(**CONFIGS[cfg_name]))
        res_pal = simulate(tr, Policy.MASA,
                           SimConfig(backend="pallas-interpret",
                                     **CONFIGS[cfg_name]))
        assert counters(res_cmd) == counters(res_pal), cfg_name


try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # collection must degrade to a skip, never hard-error
    @pytest.mark.skip(reason="hypothesis not installed; fuzz variant skipped")
    def test_stacked_fuzz():
        pass
else:
    @settings(max_examples=12, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.sampled_from(range(len(COMBOS))),
           st.integers(1, 16), st.sampled_from(BACKENDS))
    def test_stacked_fuzz(seed, combo_idx, mlp, backend):
        policy, cfg_name = COMBOS[combo_idx]
        _assert_stacked_matches(seed=seed, policy=policy, cfg_name=cfg_name,
                                mlp=mlp, backend=backend)
