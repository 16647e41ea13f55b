"""Plain reference of the simulator's semantics: traces and counters.

Written from the stated rules, one request at a time in plain Python ints,
and importing nothing of the program under test. The benchmark compares
what the timed sweeps produced, cell by cell, with what this module gives
for the same cell:

* :func:`generate_trace` rebuilds a workload's request stream from its
  profile and the sweep's seed: the calibrated Markov generator of the
  paper's workload suite, with the golden-ratio row -> subarray hash.
* :func:`simulate` serves one core's stream in program order against the
  bank / subarray timing rules of the five policies (BASELINE, SALP-1,
  SALP-2, MASA, Ideal), with refresh off or under DARP.
* :func:`simulate_mix` serves several cores sharing the channel, one
  request per step, chosen by FR-FCFS or TCM among the cores' head
  requests, and gives the per-core completion cycles and the weighted
  speedup against each core run alone on the baseline.

Every quantity is a cycle count in the DRAM command clock. The timing
constants come from the configuration file (``timing``). ``faw=False``
drops the four-activate window: that is the benchmark's control, a
simulator that breaks one stated JEDEC rule.
"""
from __future__ import annotations

import zlib

import numpy as np

POLICIES = ("BASELINE", "SALP1", "SALP2", "MASA", "IDEAL")
NEG = -1
#: Knuth's 2^32 / phi, the golden-ratio row -> subarray hash multiplier.
GOLDEN_MULT = 2654435761
#: Scheduler tier spacing and the key of an exhausted core.
BIG = 1 << 28
DEAD = 2_000_000_000

COUNTERS = ("total_cycles", "n_requests", "n_act", "n_pre", "n_rd", "n_wr",
            "n_sasel", "n_hit", "sum_latency", "n_reads", "sa_open_cycles")


def mlp_window(mpki: float, core: dict) -> int:
    """Outstanding misses a full ROB allows at this miss density."""
    return max(1, min(core["mshr"], int(round(core["rob"] * mpki / 1000.0))))


def generate_trace(profile: dict, n: int, seed: int, config: dict,
                   row_space_offset: int = 0) -> dict:
    """One workload's request stream: lists ``bank``, ``subarray``, ``row``,
    ``is_write``, ``gap``, ``dep`` and the scalar ``mlp_window``.

    ``profile`` holds the workload's fields (``name``, ``mpki``,
    ``wr_frac``, ``row_run``, ``n_streams``, ``rows_per_stream``,
    ``dep_frac``, ``seq_frac``, ``cold_frac``, ``align``); ``config`` the
    geometry (``n_banks``, ``n_subarrays``, ``rows_per_bank``) and the
    analytic core (``core``) that paces the stream. The random draws are
    made in a fixed order from ``default_rng(SeedSequence([seed,
    crc32(name)]))``, so one seed gives one stream.
    """
    nb, rpb, core = config["n_banks"], config["rows_per_bank"], config["core"]
    rng = np.random.default_rng(
        np.random.SeedSequence([seed, zlib.crc32(profile["name"].encode())]))
    k, rps = profile["n_streams"], profile["rows_per_stream"]
    align, seq_frac = profile["align"], profile["seq_frac"]
    hot_bank = rng.integers(0, nb, size=(k, rps))
    if align > 0:
        shared = rng.integers(0, nb, size=rps)
        collide = rng.random((k, rps)) < align
        hot_bank = np.where(collide, shared[None, :], hot_bank)
    hot_row = (rng.integers(0, rpb, size=(k, rps)) + row_space_offset) % rpb
    hot_bank, hot_row = hot_bank.tolist(), hot_row.tolist()
    cur = rng.integers(0, rps, size=k).tolist()
    seq_row = rng.integers(0, rpb, size=k).tolist()
    seq_bank = rng.integers(0, nb, size=k).tolist()
    in_seq = [False] * k
    pick = rng.integers(0, k, size=n).tolist()
    switch = rng.random(n).tolist()
    seq_draw = rng.random(n).tolist()
    cold = rng.random(n).tolist()
    jump = rng.integers(0, rps, size=n).tolist()
    cold_bank = rng.integers(0, nb, size=n).tolist()
    cold_row = rng.integers(0, rpb, size=n).tolist()
    p_switch = 1.0 / max(profile["row_run"], 1.0)

    bank, row = [0] * n, [0] * n
    for i in range(n):
        s = pick[i]
        if cold[i] < profile["cold_frac"]:
            bank[i] = cold_bank[i]
            row[i] = (cold_row[i] + row_space_offset) % rpb
            continue
        if switch[i] < p_switch:
            if seq_draw[i] < seq_frac:
                if not in_seq[s]:
                    in_seq[s] = True
                    seq_row[s] = hot_row[s][cur[s]]
                    seq_bank[s] = hot_bank[s][cur[s]]
                seq_row[s] = (seq_row[s] + 1) % rpb
                if seq_draw[i] > align * seq_frac:
                    seq_bank[s] = (seq_bank[s] + 1) % nb
            else:
                in_seq[s] = False
                cur[s] = jump[i]
        if in_seq[s]:
            bank[i], row[i] = seq_bank[s], seq_row[s]
        else:
            bank[i], row[i] = hot_bank[s][cur[s]], hot_row[s][cur[s]]

    # golden mapping: row and bank are kept, the subarray hashes the row
    subarray = [((r * GOLDEN_MULT) >> 11) % config["n_subarrays"] for r in row]
    is_write = (rng.random(n) < profile["wr_frac"]).tolist()
    dep_draw = rng.random(n).tolist()
    dep = [d < profile["dep_frac"] and not w for d, w in zip(dep_draw, is_write)]
    dep[0] = False
    mean_gap = (1000.0 / profile["mpki"]) / (core["ipc_peak"]
                                             * core["cpu_per_dram"])
    gap = np.maximum(0, np.round(rng.exponential(mean_gap, size=n)))
    gap = [int(g) for g in gap]
    gap[0] = 0
    return dict(bank=bank, subarray=subarray, row=row, is_write=is_write,
                gap=gap, dep=dep, mlp_window=mlp_window(profile["mpki"], core))


class Channel:
    """One channel's banks and bus, served one request at a time."""

    def __init__(self, policy: str, config: dict, faw: bool = True):
        timing = config["timing"]
        n_banks, n_subarrays = config["n_banks"], config["n_subarrays"]
        if policy == "IDEAL":
            # every subarray becomes a bank of its own, under baseline rules
            n_banks, n_subarrays, policy = n_banks * n_subarrays, 1, "BASELINE"
        self.policy, self.t, self.faw = policy, timing, faw
        self.nb, self.ns = n_banks, n_subarrays
        # per subarray: [open_row, act_done, ras_done, wrr_done, pre_done]
        self.sa = [[[NEG, 0, 0, 0, 0] for _ in range(n_subarrays)]
                   for _ in range(n_banks)]
        self.designated = [NEG] * n_banks   # MASA: subarray on the bitlines
        self.open_sa = [NEG] * n_banks      # others: the activated subarray
        self.last_act = [0] * n_banks
        self.acts = [0, 0, 0, 0]            # last four ACT cycles, oldest first
        self.col_last, self.col_last_wr = -(10 ** 6), False
        self.wr_data_end = self.bus_free = 0
        self.last_open_time = self.open_count = 0
        self.c = dict.fromkeys(COUNTERS, 0)
        self.max_comp = 0

    def serve(self, b: int, s: int, w: int, is_wr: bool, vis: int,
              close_bank: bool = False) -> int:
        """Serve one request visible at ``vis``; return its completion.

        ``close_bank``: a refresh of this bank follows the access and
        closes every row of the bank.
        """
        t, pol = self.t, self.policy
        masa = pol == "MASA"
        bank = self.sa[b]
        own = bank[s]
        os_ = self.open_sa[b]
        hit = own[0] == w
        act = not hit
        pre_own = own[0] != NEG and act
        pre_oth = not masa and os_ != NEG and os_ != s and act
        oth = bank[os_] if pre_oth else None
        t_pre_own = max(vis, own[2], own[3])
        t_pre_oth = max(vis, oth[2], oth[3]) if pre_oth else 0

        t_act = max(vis, own[4], self.last_act[b] + t["t_rrd_sa"],
                    self.acts[3] + t["t_rrd"])
        if self.faw:
            t_act = max(t_act, self.acts[0] + t["t_faw"])
        if pre_own:
            t_act = max(t_act, t_pre_own + t["t_rp"])
        if pre_oth and pol == "BASELINE":
            t_act = max(t_act, t_pre_oth + t["t_rp"])
        elif pre_oth and pol == "SALP1":
            t_act = max(t_act, t_pre_oth + 1)

        t_col = max(vis, own[1]) if hit else t_act + t["t_rcd"]
        if pre_oth and pol == "SALP2":
            t_col = max(t_col, t_pre_oth + 1)
        sasel = masa and hit and self.designated[b] != s
        if sasel:
            t_col += t["t_sa"]
        t_col = max(t_col, self.col_last + t["t_ccd"])
        if not is_wr and self.col_last_wr:
            t_col = max(t_col, self.wr_data_end + t["t_wtr"])
        if is_wr and not self.col_last_wr:
            t_col = max(t_col, self.col_last + t["t_rtw"])
        lat = t["t_cwl"] if is_wr else t["t_cl"]
        t_col = max(t_col, self.bus_free - lat)
        data_end = t_col + lat + t["t_bl"]
        comp = t_col if is_wr else data_end

        # time-integral of the activated subarrays beyond the first
        c = self.c
        c["sa_open_cycles"] += (max(self.open_count - 1, 0)
                                * max(t_col - self.last_open_time, 0))
        self.last_open_time = max(t_col, self.last_open_time)
        self.open_count += int(act) - int(pre_oth) - int(pre_own)

        if pre_oth:
            oth[0] = NEG
            oth[4] = t_pre_oth + t["t_rp"]
        if pre_own:
            own[4] = t_pre_own + t["t_rp"]
        if act:
            own[0], own[1], own[2], own[3] = (w, t_act + t["t_rcd"],
                                              t_act + t["t_ras"], 0)
            self.last_act[b] = t_act
            self.acts = self.acts[1:] + [t_act]
        if is_wr:
            own[3] = max(own[3], data_end + t["t_wr"])
        else:
            own[2] = max(own[2], t_col + t["t_rtp"])
        if not masa:
            self.open_sa[b] = s
        self.designated[b] = s
        if close_bank:
            for row in bank:
                row[0] = NEG

        self.col_last, self.col_last_wr = t_col, is_wr
        if is_wr:
            self.wr_data_end = data_end
        self.bus_free = data_end
        c["n_act"] += act
        c["n_pre"] += pre_oth + pre_own
        c["n_rd"] += not is_wr
        c["n_reads"] += not is_wr
        c["n_wr"] += is_wr
        c["n_sasel"] += sasel
        c["n_hit"] += hit
        if not is_wr:
            c["sum_latency"] += comp - vis
        self.max_comp = max(self.max_comp, comp)
        return comp


class Darp:
    """Per-bank DARP refresh (per-bank bursts of ``t_rfc_pb``): deadlines
    every ``t_refi`` staggered over the banks, owed refreshes drained in
    idle gaps and write shadows, forced in front of a request only past the
    postpone window."""

    def __init__(self, timing: dict, n_banks: int):
        t = timing
        self.t = t
        step = max(t["t_refi"] // max(n_banks, 1), 1)
        self.due = [b * step + t["t_refi"] for b in range(n_banks)]
        self.busy = [0] * n_banks
        self.debt = [0] * n_banks
        self.last_end = [0] * n_banks

    def visibility(self, b: int, vis: int, is_wr: bool):
        """Gate a request to bank ``b``; return (vis, plan for commit)."""
        t = self.t
        rfc, refi = t["t_rfc_pb"], t["t_refi"]
        busy = self.busy[b]
        vis = max(vis, busy)
        due = self.due[b]
        crossings = (vis - due) // refi + 1 if vis >= due else 0
        owed = self.debt[b] + crossings
        new_due = due + crossings * refi
        launch = max(self.last_end[b], busy) + rfc
        avail = max(vis - launch, 0)
        n_idle = min(owed, (avail + rfc - 1) // rfc)
        drain_end = launch + n_idle * rfc
        if n_idle > 0:
            vis = max(vis, drain_end)
        owed -= n_idle
        n_forced = max(owed - t["ref_postpone_max"], 0)
        vis += n_forced * rfc
        owed -= n_forced
        chain_end = vis if n_forced > 0 else drain_end
        shadow = is_wr and owed >= 2
        plan = dict(due=new_due, debt=owed - int(shadow),
                    chain=chain_end if (n_idle > 0 or n_forced > 0) else 0,
                    shadow=shadow,
                    close=n_idle > 0 or n_forced > 0 or shadow)
        return vis, plan

    def commit(self, b: int, plan: dict, comp: int) -> None:
        shadow_end = comp + self.t["t_rfc_pb"] if plan["shadow"] else 0
        self.busy[b] = max(self.busy[b], plan["chain"], shadow_end)
        self.due[b] = plan["due"]
        self.debt[b] = plan["debt"]
        self.last_end[b] = max(self.last_end[b], comp)


def _ideal(tr: dict, n_subarrays: int) -> tuple[list, list]:
    return ([b * n_subarrays + s for b, s in zip(tr["bank"], tr["subarray"])],
            [0] * len(tr["bank"]))


def simulate(tr: dict, policy: str, config: dict, faw: bool = True) -> dict:
    """One core's stream in program order; returns the counters.

    ``config`` holds ``timing``, ``n_banks``, ``n_subarrays`` and
    ``refresh_policy`` (``"none"`` or ``"darp"``).
    """
    refresh_policy = config["refresh_policy"]
    if refresh_policy not in ("none", "darp"):
        raise ValueError(f"reference models refresh 'none' and 'darp', "
                         f"not {refresh_policy!r}")
    ch = Channel(policy, config, faw=faw)
    bank, sub = (_ideal(tr, config["n_subarrays"]) if policy == "IDEAL"
                 else (tr["bank"], tr["subarray"]))
    ref = Darp(config["timing"], ch.nb) if refresh_policy == "darp" else None
    mlp, comps = tr["mlp_window"], []
    vis = 0
    for i in range(len(bank)):
        comp_prev = comps[i - 1] if i >= 1 else 0
        rob = comps[i - mlp] if i >= mlp else 0
        vis = max(vis + tr["gap"][i], comp_prev if tr["dep"][i] else 0, rob)
        b, wr = bank[i], tr["is_write"][i]
        plan = None
        if ref is not None:
            vis, plan = ref.visibility(b, vis, wr)
        comp = ch.serve(b, sub[i], tr["row"][i], wr, vis,
                        close_bank=plan is not None and plan["close"])
        if plan is not None:
            ref.commit(b, plan, comp)
        comps.append(comp)
    out = dict(ch.c)
    out["n_requests"] = len(bank)
    out["total_cycles"] = max(ch.max_comp, vis)
    return out


def simulate_mix(traces: list[dict], mpkis: list[float], policy: str,
                 scheduler: str, config: dict, faw: bool = True) -> dict:
    """Cores sharing the channel (refresh off); one request per step.

    Each step every core with requests left offers its head request,
    visible at its own pacing; the scheduler keys them and the smallest
    key is served (ties to the lowest core). FR-FCFS puts row hits that
    are already queued (visible by the time the data bus frees) ahead of
    everything else; TCM adds a rank boost for the queued requests of the
    lower-MPKI half of the cores. Returns the shared counters, each core's
    last completion (``core_cycles``), its run-alone baseline cycles
    (``alone_cycles``) and the weighted speedup.
    """
    if scheduler not in ("FRFCFS", "TCM"):
        raise ValueError(f"reference models FRFCFS and TCM, not {scheduler!r}")
    if config["refresh_policy"] != "none":
        raise ValueError("the reference's mixes run with refresh off")
    C, N = len(traces), len(traces[0]["bank"])
    ch = Channel(policy, config, faw=faw)
    streams = [_ideal(tr, config["n_subarrays"]) if policy == "IDEAL"
               else (tr["bank"], tr["subarray"]) for tr in traces]
    order = sorted(range(C), key=lambda c: (mpkis[c], c))
    rank = [order.index(c) for c in range(C)]
    ptr, vis_prev, max_comp = [0] * C, [0] * C, [0] * C
    comps: list[list[int]] = [[] for _ in range(C)]
    for _ in range(C * N):
        best, best_key, best_vis = -1, None, 0
        for c in range(C):
            p = ptr[c]
            if p >= N:
                continue
            tr = traces[c]
            mlp = tr["mlp_window"]
            comp_prev = comps[c][p - 1] if p >= 1 else 0
            rob = comps[c][p - mlp] if p >= mlp else 0
            vis = max(vis_prev[c] + tr["gap"][p],
                      comp_prev if tr["dep"][p] else 0, rob)
            b, s = streams[c][0][p], streams[c][1][p]
            queued = vis <= ch.bus_free
            key = vis + (0 if queued and ch.sa[b][s][0] == tr["row"][p]
                         else BIG)
            if scheduler == "TCM" and queued and rank[c] < C // 2:
                key -= 2 * BIG
            if best_key is None or key < best_key:
                best, best_key, best_vis = c, key, vis
        c, p = best, ptr[best]
        comp = ch.serve(streams[c][0][p], streams[c][1][p],
                        traces[c]["row"][p], traces[c]["is_write"][p],
                        best_vis)
        comps[c].append(comp)
        ptr[c] += 1
        vis_prev[c] = best_vis
        max_comp[c] = max(max_comp[c], comp)
    out = dict(ch.c)
    out["n_requests"] = C * N
    out["total_cycles"] = max(ch.max_comp, max(vis_prev))
    alone = [float(simulate(tr, "BASELINE", config, faw=faw)["total_cycles"])
             for tr in traces]
    core = [float(x) for x in max_comp]
    return dict(counters=out, core_cycles=[int(x) for x in max_comp],
                alone_cycles=alone,
                weighted_speedup=float(np.sum(
                    np.asarray(alone) / np.maximum(np.asarray(core), 1))))
