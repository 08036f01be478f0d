"""End-to-end dry-run machinery test on a small fake mesh (subprocess —
the device-count override must precede jax init, so it cannot run in this
process)."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.configs.base import InputShape
    from repro.configs.registry import get_config
    from repro.core.hlo_analysis import analyze_hlo
    from repro.core.roofline import build_report
    from repro.launch.sharding import ShardingRules
    from repro.launch.specs import lowering_args
    from repro.models.model import Model
    from repro.train.loop import TrainConfig

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_config("qwen3-0.6b-reduced")
    model = Model(cfg)
    results = {}
    for shape in (InputShape("t", 64, 8, "train"),
                  InputShape("p", 64, 8, "prefill"),
                  InputShape("d", 64, 8, "decode")):
        step, args = lowering_args(model, shape, TrainConfig(remat=True))
        rules = ShardingRules(mesh, train=(shape.kind == "train"),
                              decode=(shape.kind == "decode"))
        if shape.kind == "train":
            insh = (rules.params(args[0]), rules.opt_state(args[1]),
                    rules.batch(args[2]))
        elif shape.kind == "prefill":
            insh = (rules.params(args[0]), rules.batch(args[1]))
        else:
            insh = (rules.params(args[0]), rules.cache(args[1], 8),
                    rules.batch(args[2]))
        with jax.set_mesh(mesh):
            compiled = jax.jit(step, in_shardings=insh).lower(*args).compile()
            txt = compiled.as_text()
        cost = analyze_hlo(txt)
        rep = build_report(cfg.name, shape, cfg, "test", 8, cost)
        results[shape.kind] = {
            "flops": cost.flops_per_chip,
            "bytes": cost.bytes_per_chip,
            "step": rep.step_time,
            "dominant": rep.dominant,
        }
    print(json.dumps(results))
""")


def test_lower_compile_roofline_on_fake_mesh():
    # the subprocess doesn't see pytest's pythonpath ini — pass src along
    env = dict(os.environ)
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=480)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(out) == {"train", "prefill", "decode"}
    for kind, row in out.items():
        assert row["flops"] > 0, (kind, row)
        assert row["bytes"] > 0, (kind, row)
        assert row["step"] > 0, (kind, row)
        assert row["dominant"] in ("compute", "memory", "collective")
    # a train step does ~3× the FLOPs of the forward-only prefill
    assert out["train"]["flops"] > 1.5 * out["prefill"]["flops"]
    # decoding ONE token is far cheaper than prefilling 64
    assert out["decode"]["flops"] < 0.2 * out["prefill"]["flops"]
