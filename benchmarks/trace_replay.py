"""Trace replay — the SLO-scheduling claim, measured.

The headline for ``BENCH_trace.json``: over one hour of the frozen
diurnal+bursty trace, the ``energy_under_slo`` scheduler **meets an
interactive ttfc-p95 target the mean-energy-optimal baseline violates,
at equal or lower energy per completed request**. The baseline is not a
strawman — it runs the same Router admission control (bounded queue,
client deadlines), just mean-optimally and SLO-blind: no priority
ordering, no per-class sheds, no quantile constraint on the count. Its
interactive tail then blows up twice over — FIFO head-of-line blocking
behind long batch prompts during bursts, and the count argmin parked at
the mean-energy optimum with no burst headroom — and the interactive
requests that die at their client deadline after queueing behind batch
work are exactly the completions the SLO run saves, which is where its
energy-per-done edge comes from.

The committed numbers run on the deterministic virtual-time simulator
(``workload/sim.py`` — real scheduler, real SLO arithmetic, bit-for-bit
reproducible; the full hour replays in seconds). ``--smoke`` replays a
short trace open-loop against the live Router/ThreadBackend stack
first, proving the wire path works, then runs a shortened simulated
comparison for the CI ``trace-replay-smoke`` lane.
"""
from __future__ import annotations

import argparse
import dataclasses

from benchmarks.common import save, save_bench, table
from repro.configs.registry import get_config
from repro.models.model import Model
from repro.serving import Router
from repro.serving.backend import ThreadBackend
from repro.serving.engine import EngineConfig
from repro.workload.replay import ReplayReport, replay
from repro.workload.sim import FleetModel, simulate
from repro.workload.slo import SLOSpec
from repro.workload.traces import get_preset, synthesize

# ---------------------------------------------------------------------------
# the frozen benchmark configuration — every number in the committed
# BENCH_trace.json derives from these and nothing else
# ---------------------------------------------------------------------------
TRACE_SEED = 1
SIM_SEED = 0
DURATION_S = 3600.0
SLO_TEXT = "interactive:0.5,batch:8.0"
# client-side deadlines (what the *users* tolerate — distinct from the
# SLO targets the operator schedules against)
DEADLINES = {"interactive": 1.2, "batch": 30.0, "default": 30.0}
SIM_KW = dict(feasible_counts=[1, 2, 3, 4], window=32, window_s=20.0,
              max_queue=64, epsilon=0.05)


def bench_trace(duration_s: float, seed: int):
    spec = dataclasses.replace(get_preset("diurnal-bursty"),
                               duration_s=duration_s,
                               max_requests=200_000)
    return synthesize(spec, seed=seed)


def run_pair(duration_s: float, smoke: bool) -> tuple[ReplayReport,
                                                      ReplayReport]:
    """The comparison: mean-energy baseline vs SLO-constrained run on
    the SAME trace, same fleet, same admission machinery."""
    trace = bench_trace(duration_s, TRACE_SEED)
    slo = SLOSpec.parse(SLO_TEXT)
    fleet = FleetModel()
    kw = dict(**SIM_KW, seed=SIM_SEED, fleet=fleet,
              deadline_by_class=DEADLINES)
    base = simulate(trace, objective="energy", **kw)
    cons = simulate(trace, objective="energy_under_slo", slo=slo, **kw)
    if not smoke:
        # the reproducibility contract: identical seed + trace must
        # reproduce the report bit-for-bit
        again = simulate(trace, objective="energy_under_slo", slo=slo, **kw)
        assert again == cons, "simulate() is not deterministic"
    return base, cons


def bench_live_smoke() -> dict:
    """Open-loop replay against the real Router + ThreadBackend: the
    wire path (trace -> Request -> priority dispatch -> per-class
    windows) exercised live, compressed 10x. Numbers are wall-clock and
    NOT comparable across hosts — rot check only."""
    import jax

    cfg = get_config("qwen3-0.6b-reduced")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    spec = dataclasses.replace(get_preset("diurnal-bursty"),
                               duration_s=40.0, max_requests=200)
    trace = synthesize(spec, seed=TRACE_SEED)
    slo = SLOSpec.parse(SLO_TEXT)
    ecfg = EngineConfig(n_slots=4, max_len=192, chunk_tokens=4)

    def factory(n):
        return ThreadBackend(model, params, n, config=ecfg)

    with Router(backend_factory=factory, feasible_counts=[1, 2],
                objective="energy_under_slo", slo=slo,
                window=8, window_s=5.0, max_queue=32,
                seed=SIM_SEED) as router:
        rep = replay(trace, router, time_scale=10.0,
                     vocab_size=cfg.vocab_size)
    assert rep.n_done > 0, "live replay completed nothing"
    return {"live_n_requests": rep.n_requests, "live_n_done": rep.n_done,
            "live_n_shed": rep.n_shed, "live_goodput_rps": rep.goodput_rps,
            "live_ttfc_p95_s": rep.ttfc_p95_s,
            "live_counts_visited": list(rep.counts_visited)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="live wire-path replay + shortened simulation")
    args = ap.parse_args()

    live = bench_live_smoke() if args.smoke else {}
    duration = 600.0 if args.smoke else DURATION_S
    base, cons = run_pair(duration, args.smoke)

    target = SLOSpec.parse(SLO_TEXT).constraint.ttfc_p95_s
    bi = base.per_class["interactive"]
    ci = cons.per_class["interactive"]
    rows = [
        ["energy (mean-optimal)", base.final_n, bi.ttfc_p95_s,
         str(base.slo_attained), base.n_done, base.goodput_rps,
         base.energy_per_done_j],
        ["energy_under_slo", cons.final_n, ci.ttfc_p95_s,
         str(cons.slo_attained), cons.n_done, cons.goodput_rps,
         cons.energy_per_done_j],
    ]
    lines = [f"# trace replay — {base.trace} "
             f"(trace seed {TRACE_SEED}, sim seed {SIM_SEED}, "
             f"{duration:.0f}s{', smoke' if args.smoke else ''})", ""]
    lines += table(["objective", "final n", "interactive p95 (s)",
                    "attained", "done", "goodput rps", "J/done"], rows)
    lines += ["", f"interactive ttfc-p95 target: {target}s; client "
              f"deadlines {DEADLINES}"]

    if not args.smoke:
        # the claim the committed artifact exists to witness
        assert cons.slo_attained, "SLO run failed its own targets"
        assert bi.ttfc_p95_s > target, \
            "baseline met the target — no violation to beat"
        assert cons.energy_per_done_j <= base.energy_per_done_j, \
            "SLO run spent more energy per completion than the baseline"

    payload = {"smoke": args.smoke, "target_ttfc_p95_s": target,
               "slo": SLO_TEXT, "deadlines": DEADLINES,
               "base": base.to_dict(), "slo_run": cons.to_dict(), **live}
    print(save("trace_replay", payload, lines))
    save_bench("trace", {
        "smoke": args.smoke, "duration_s": duration,
        "trace_seed": TRACE_SEED, "sim_seed": SIM_SEED,
        "target_ttfc_p95_s": target,
        "base_final_n": base.final_n,
        "base_interactive_ttfc_p95_s": bi.ttfc_p95_s,
        "base_n_done": base.n_done,
        "base_goodput_rps": base.goodput_rps,
        "base_energy_per_done_j": base.energy_per_done_j,
        "slo_final_n": cons.final_n,
        "slo_interactive_ttfc_p95_s": ci.ttfc_p95_s,
        "slo_attained": bool(cons.slo_attained),
        "slo_n_done": cons.n_done,
        "slo_goodput_rps": cons.goodput_rps,
        "slo_energy_per_done_j": cons.energy_per_done_j,
        **live})


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
