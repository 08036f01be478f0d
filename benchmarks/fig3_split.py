"""Fig. 3 — n containers: normalised time / energy / power.

Three columns of evidence:
  (a) paper's fitted models evaluated (ground truth being reproduced),
  (b) calibrated TX2/Orin device simulators (our §VI reproduction),
  (c) REAL measurements on the host CPU testbed (pinned processes).
All normalised to the 1-container benchmark, as in the paper.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import save, table
from repro.core import testbed
from repro.core.energy_model import (PAPER_MODELS, eval_model, orin_model,
                                     tx2_model)


def run(quick: bool = False) -> str:
    payload: dict = {"devices": {}, "measured": []}
    rows = []
    for name, dev, n_max in (("tx2", tx2_model(), 6),
                             ("orin", orin_model(), 12)):
        ns = list(range(1, n_max + 1))
        t1, e1, p1 = dev.time(1), dev.energy(1), dev.power(1)
        sim = {"n": ns,
               "time": [dev.time(n) / t1 for n in ns],
               "energy": [dev.energy(n) / e1 for n in ns],
               "power": [dev.power(n) / p1 for n in ns]}
        paper = {m: eval_model(*PAPER_MODELS[(name, m)][0:1],
                               PAPER_MODELS[(name, m)][1], np.array(ns))
                 for m in ("time", "energy", "power")}
        payload["devices"][name] = {"sim": sim,
                                    "paper": {k: v.tolist()
                                              for k, v in paper.items()}}
        for i, n in enumerate(ns):
            rows.append([name, n, sim["time"][i], float(paper["time"][i]),
                         sim["energy"][i], float(paper["energy"][i]),
                         sim["power"][i], float(paper["power"][i])])

    lines = ["# Fig. 3 — n containers (normalised to 1-container benchmark)",
             "", "## TX2 / Orin: simulator vs paper's fitted models", ""]
    lines += table(["device", "n", "t sim", "t paper", "E sim", "E paper",
                    "P sim", "P paper"], rows)

    # ---- real host measurements
    n_frames = 64 if quick else 192
    total_cores = 8
    frames = testbed.make_video(n_frames)
    base = testbed.run_split(frames, 1, total_cores=total_cores)
    meas_rows = []
    for n in (1, 2, 4, 8):
        # allow_shared: on hosts with fewer than 8 cores the high counts
        # time-share cores (explicitly — run_split refuses silent overlap)
        r = testbed.run_split(frames, n, total_cores=total_cores,
                              allow_shared=True)
        ok = bool(np.allclose(r.outputs, base.outputs, atol=1e-5))
        payload["measured"].append(
            {"n": n, "wall_s": r.wall_s, "power_w": r.avg_power_w,
             "energy_j": r.energy_j, "outputs_match": ok,
             "disjoint_cores": r.disjoint})
        meas_rows.append([n, r.wall_s / base.wall_s,
                          r.energy_j / base.energy_j,
                          r.avg_power_w / base.avg_power_w,
                          "✓" if ok else "✗"])
    lines += ["", f"## Host CPU testbed (real CPU wall times, {total_cores} "
              f"cores, {n_frames} frames)", ""]
    lines += table(["n", "time (norm)", "energy (norm)", "power (norm)",
                    "outputs=="], meas_rows)

    # ---- serving-pool analogue: threads on the shared device (the LM
    # counterpart of the pinned-process video testbed above)
    import jax

    from benchmarks import pool_scaling
    from repro.models.model import Model

    cfg = pool_scaling.bench_config()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = pool_scaling.make_requests(cfg, 8 if quick else 16,
                                      4 if quick else 8)
    pool_rows = pool_scaling.measure_pool(model, params, reqs,
                                          reps=1 if quick else 2)
    payload["serving_pool"] = pool_rows
    base_w = pool_rows[0]["wall_seq_s"]
    lines += ["", "## Serving pool (REAL wall times, threaded engines on "
              "the shared device)", ""]
    lines += table(["n", "seq (norm)", "conc (norm)", "speedup"],
                   [[r["n"], r["wall_seq_s"] / base_w,
                     r["wall_conc_s"] / base_w, r["speedup"]]
                    for r in pool_rows])
    return save("fig3_split", payload, lines)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    print(run())
