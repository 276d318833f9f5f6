"""``chip_smoke.py``'s phases and reference checks, run on the CPU at a
small scale.  The chip runs the same functions at graph500-22; this keeps
their logic and their comparisons with scipy honest in every test run.

Scale 12 rather than 10: the tiered phase needs a cut whose shards hold
edges, and at scale 10 the 1,536 padded vertices span only three blocks
of 512.
"""

import json
import os
import shutil
import subprocess
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

SCALE = 12


def _lines(out):
    return [json.loads(x) for x in out.splitlines() if x.startswith("{")]


def test_one_chip_phases_agree_with_references(capsys):
    chip_smoke.run(jax.devices(), scale=SCALE, seed=0, four_chips=False,
                   nshards=4, resident_shards=2)
    recs = _lines(capsys.readouterr().out)
    assert [r["phase"] for r in recs] == [
        "device", "resident", "tiered", "serve", "dynamic"]
    res, tier, serve, dyn = recs[1:]
    assert res["bfs"]["exact"] and res["cc"]["same_partition"]
    assert res["sssp"]["same_reach"]
    assert res["sssp"]["max_rel_err"] <= res["sssp"]["rtol"]
    assert res["pagerank"]["l1_err"] <= res["pagerank"]["l1_bound"]
    assert tier["bfs"]["bitwise"] and tier["pagerank"]["allclose"]
    assert tier["bfs"]["stats"]["shards_streamed"] > tier["nshards"]
    assert serve["exact"] == serve["requests"] == chip_smoke.SERVE_REQUESTS
    assert dyn["bitwise"] and dyn["exact"] and dyn["inserted"] > 0


def test_main_refuses_a_cpu_backend(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--four-chips"]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""                # no phase, no result line
    assert "TPU" in captured.err and "cpu" in captured.err


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_four_chip_phase_on_four_host_devices():
    """The sharded path on 4 forced host devices: BFS bitwise equal to one
    device, PageRank within its bound, one edge shard per device."""
    code = (f"import sys; sys.path.insert(0, {ROOT!r}); import jax, "
            "chip_smoke; chip_smoke.run(jax.devices(), "
            f"scale={SCALE}, seed=1, four_chips=True)")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stderr[-3000:]
    rec = _lines(out.stdout)[-1]
    assert rec["phase"] == "four_chips"
    assert rec["edge_shard_devices"] == [0, 1, 2, 3]
    assert rec["bfs"]["bitwise_vs_one_device"] and rec["bfs"]["exact"]
    assert rec["pagerank"]["l1_err"] <= rec["pagerank"]["l1_bound"]


def test_script_alone_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=300,
                         env=_env())
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
