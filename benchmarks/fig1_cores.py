"""Fig. 1 — single container, varying CPU allocation.

Two views:
  (a) the calibrated TX2/Orin analytic device models (paper's own hardware),
  (b) a REAL measurement on this host's CPU testbed (one pinned container,
      1..8 cores) — demonstrating the same flattening with real wall times.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import save, table
from repro.core import testbed
from repro.core.energy_model import orin_model, tx2_model


def run(quick: bool = False) -> str:
    rows, payload = [], {"model": {}, "measured": []}
    for name, dev in (("tx2", tx2_model()), ("orin", orin_model())):
        cores = np.linspace(0.5, dev.cores, 8)
        ts = [dev.single_container_time(float(c)) for c in cores]
        es = [dev.p_idle_w * t + dev.p_core_w * min(c, dev.cores) * t * 0.9
              for c, t in zip(cores, ts)]
        payload["model"][name] = {"cores": cores.tolist(), "time_s": ts,
                                  "energy_j": es}
        for c, t, e in zip(cores, ts, es):
            rows.append([f"{name} (model)", f"{c:.1f}", t, e])

    n_frames = 48 if quick else 120
    frames = testbed.make_video(n_frames)
    for c in (1, 2, 4, 8):
        wall = testbed.run_single_container(frames, cores=c)
        energy = (testbed.P_IDLE_W + testbed.P_CORE_W * c * 0.9) * wall
        payload["measured"].append({"cores": c, "time_s": wall,
                                    "energy_j": energy})
        rows.append(["host (measured)", str(c), wall, energy])

    lines = ["# Fig. 1 — one container, varying CPU cores (CPU wall times)",
             ""]
    lines += table(["device", "cores", "time (s)", "energy (J)"], rows)
    t1 = payload["measured"][0]["time_s"]
    t8 = payload["measured"][-1]["time_s"]
    lines += ["", f"host speedup 1→8 cores: {t1 / t8:.2f}× "
              "(sub-linear — the flattening that motivates splitting)"]
    return save("fig1_cores", payload, lines)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    print(run())
