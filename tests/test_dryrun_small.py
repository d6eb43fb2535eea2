"""Dry-run machinery on a small fake-device mesh (subprocess: the 8-device
XLA flag must be set before jax init, which pytest has already done)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.launch import dryrun as dr
    from repro.launch.shapes import InputShape

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    out = {}
    for arch in ("llama3_8b", "olmoe_1b_7b", "xlstm_1_3b",
                 "recurrentgemma_2b", "qwen2_vl_7b", "musicgen_medium"):
        cfg = get_config(arch, "reduced")
        shape = InputShape("t", 64 if cfg.family != "vlm" else 64, 8, "train")
        jitted, args, model = dr.build_train(cfg, shape, mesh, "dense", "mp")
        with jax.set_mesh(mesh):
            compiled = jitted.lower(*args).compile()
        c = compiled.cost_analysis()
        out[arch + "/train"] = float(c.get("flops", 0))
        dshape = InputShape("d", 64, 8, "decode")
        jitted, args, model = dr.build_decode(cfg, dshape, mesh)
        with jax.set_mesh(mesh):
            compiled = jitted.lower(*args).compile()
        out[arch + "/decode"] = float(compiled.cost_analysis().get("flops", 0))
    # gossip schedule lowers too
    cfg = get_config("llama3_8b", "reduced")
    shape = InputShape("t", 64, 8, "train")
    jitted, args, model = dr.build_train(cfg, shape, mesh, "gossip", "mp")
    with jax.set_mesh(mesh):
        compiled = jitted.lower(*args).compile()
    stats = __import__("repro.launch.hlo_analysis",
                       fromlist=["collective_stats"]).collective_stats(
        compiled.as_text())
    out["gossip/collective_permute"] = stats["collective-permute"]["count"]
    print("RESULT " + json.dumps(out))
""")


@pytest.mark.slow
def test_dryrun_small_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout[-2000:]
    out = json.loads(line[0][len("RESULT "):])
    for k, v in out.items():
        if k.endswith(("train", "decode")):
            assert v > 0, (k, v)
    # the gossip schedule must actually emit collective_permutes
    assert out["gossip/collective_permute"] > 0
