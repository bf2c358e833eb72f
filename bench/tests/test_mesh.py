"""A cell on four chips is served tensor-parallel over them, with no
mesh in its configuration: the harness builds the serving mesh from the
cell's ``chips``, draws each weight where the engine places it, and the
run's check holds, on four CPU devices.  Run in a subprocess, because the
device count is fixed when JAX starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/tests/test_mesh.py <seed>

prints the run's checks, the control's ``correct`` on the same run, and
for every parameter leaf the fraction of it that each device holds."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
NAME = "tiny-mesh.saturated"
SEED = 2 ** 34 + 5


def main(seed: int) -> None:
    import jax
    import numpy as np

    import cell
    from reference import control_gaps, served_gaps
    from tests_common import DATA

    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    leaves = {}

    def look(eng):
        assert dict(eng.mesh.shape) == {"data": 1, "model": 4}, eng.mesh
        assert set(eng.mesh.devices.flat) == set(jax.devices()[:4])
        for path, x in jax.tree_util.tree_leaves_with_path(eng.params):
            leaves[jax.tree_util.keystr(path)] = {
                "model_sharded": "model" in str(x.sharding.spec),
                "fractions": [float(np.prod(s.data.shape) / x.size)
                              for s in x.addressable_shards]}

    def run(gaps=None):
        return cell.run_cell(bench, NAME, seed, 2.0, False,
                             t_start=time.perf_counter(), device=None,
                             data=DATA, stage=look, gaps=gaps)

    res = run()
    got = {}

    def program_then_control(spec, s, samples, **kw):
        got["program"] = float(np.max(served_gaps(spec, s, samples, **kw)))
        return control_gaps(spec, s, samples, **kw)

    ctrl = run(program_then_control)
    print(json.dumps({"correct": res["correct"], "checks": res["checks"],
                      "device": res["device"], "leaves": leaves,
                      "control_correct": ctrl["correct"],
                      "control_gap": ctrl["checks"]["max_logit_gap"]["value"],
                      "program_gap": got["program"]}))


def test_mesh_cell_is_sharded_and_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, __file__, str(SEED)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["control_correct"] is False
    sharded = {k: v for k, v in out["leaves"].items() if v["model_sharded"]}
    # every projection, the MLP and the (tied) embedding; norm scales are
    # replicated
    assert len(sharded) == 8, sorted(out["leaves"])
    for k, v in sharded.items():
        assert v["fractions"] == [0.25] * 4, k
    for k, v in out["leaves"].items():
        if not v["model_sharded"]:
            assert v["fractions"] == [1.0] * 4, k


if __name__ == "__main__":
    sys.path[:0] = [str(BENCH), str(BENCH / "tests"), str(BENCH.parent / "src")]
    main(int(sys.argv[1]))
