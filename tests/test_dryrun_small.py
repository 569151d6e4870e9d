"""Integration: the dry-run machinery (shardings, lowering, compile, HLO
analysis) on a reduced multi-pod mesh in a subprocess (own device count)."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, {src!r})
from repro.configs import reduced_config, ShapeConfig, TrainConfig
from repro.launch.dryrun import build_cell
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
out = {{}}
tcfg = TrainConfig(microbatch=2, remat="full")
for arch in ["qwen3_moe_30b_a3b", "recurrentgemma_2b"]:
    cfg = reduced_config(arch)
    for sname, sh in [("train", ShapeConfig("t", 32, 8, "train")),
                      ("decode", ShapeConfig("d", 64, 8, "decode"))]:
        _, jitted, args = build_cell(arch, "", mesh, cfg=cfg, shape=sh,
                                     tcfg=tcfg)
        compiled = jitted.lower(*args).compile()
        h = analyze_hlo(compiled.as_text())
        out[f"{{arch}}:{{sname}}"] = {{
            "dot_flops": h["dot_flops"],
            "wire_bytes": h["collective_wire_bytes"],
            "whiles": len(h["while_trips"]),
        }}
print("RESULT" + json.dumps(out))
"""


@pytest.mark.slow
def test_dryrun_reduced_multipod(tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _SCRIPT.format(src=os.path.abspath(src))
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT")][0]
    res = json.loads(line[len("RESULT"):])
    assert len(res) == 4
    for cell, r in res.items():
        assert r["dot_flops"] > 0, cell
        assert r["wire_bytes"] > 0, cell           # collectives present
    # train does more compute than decode
    assert res["qwen3_moe_30b_a3b:train"]["dot_flops"] > \
        10 * res["qwen3_moe_30b_a3b:decode"]["dot_flops"]
