"""chip_smoke.py: its phases at a tiny size on the CPU, and its refusal to
report success anywhere but on a TPU."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_main_path_checks_pass_at_tiny_size(tmp_path, capsys):
    got = chip_smoke.run_main_path(tmp_path, scale=10, edges=3000, k=4,
                                   chunk_edges=512, iters=3, seed=0)
    out = capsys.readouterr().out
    assert "unassigned=0" in out and "max rel err" in out
    assert "setup: XLA compile or cache load" in out
    assert got["rd"] >= 1.0 and got["superstep_s"] > 0
    # Timed lines name the platform they were taken on, never "chip" here.
    assert "measured on cpu" in out and "measured on chip" not in out


def test_small_graph_parity_holds_at_tiny_size(tmp_path, capsys):
    chip_smoke.run_small_parity(tmp_path, k=4, seed=0, scale=0.01)
    out = capsys.readouterr().out
    assert "bit-identical to their numpy oracles" in out
    assert "file path bit-identical" in out


def test_whole_scan_calls_edge_count_runs_that_many_calls(tmp_path, capsys):
    """The default stream length is a whole number of ring scan calls, so
    no call runs its full step count for a few leftover edges."""
    steps = chip_smoke.scan_steps(4, 512)
    chip_smoke.run_main_path(tmp_path, scale=10, edges=3 * steps, k=4,
                             chunk_edges=512, iters=2, seed=0)
    assert f"scan_calls=3 x {steps} steps" in capsys.readouterr().out
    assert chip_smoke.SCAN_CALLS * chip_smoke.scan_steps(
        chip_smoke.K, chip_smoke.CHUNK_EDGES) == 4 * 65280


def test_adwise_step_trace_reproduces_the_scan(capsys):
    """The backend comparison's step trace takes the scan's own decisions,
    and its rebuilt best score is the best entry at every step."""
    from repro.core import run_partitioner
    from repro.graph import make_graph

    e, n = make_graph("brain_like", seed=0, scale=0.01)
    mem = run_partitioner("adwise", e, n, 4, seed=0).assign
    tr = chip_smoke.adwise_step_trace(e, n, 4, 0)
    np.testing.assert_array_equal(tr["assign"], mem)
    assert (tr["top"] >= tr["second"]).all() and (tr["top"] > -1e29).all()
    chip_smoke.compare_adwise_backends(e, n, 4, 0, mem, jax.devices("cpu")[0])
    out = capsys.readouterr().out
    assert f"{len(e)} of {len(e)} assignments equal" in out
    assert "reproduces run_partitioner on cpu: True, on cpu: True" in out
    assert "same decisions at every step" in out


def test_pagerank_reference_matches_engine(tiny_graph):
    from repro.core import run_partitioner
    from repro.engine import build_partitioned_graph, pagerank

    edges, n = tiny_graph
    assign = run_partitioner("hdrf", edges, n, 4).assign
    pr, _ = pagerank(build_partitioned_graph(edges, assign, n, 4), iters=7)
    ref = chip_smoke.pagerank_reference(edges, n, 7)
    np.testing.assert_allclose(pr, ref, rtol=1e-5)


def test_main_refuses_without_tpu(tmp_path, capsys):
    assert jax.devices()[0].platform != "tpu"
    rc = chip_smoke.main(["--run-dir", str(tmp_path)])
    out = capsys.readouterr()
    assert rc != 0
    assert '"ok": true' not in out.out
    assert "no TPU" in out.err


def test_four_chip_phase_on_four_cpu_devices(tmp_path):
    """The --four-chip comparisons on 4 virtual CPU devices (a child
    process: the device count is fixed at JAX start-up)."""
    prog = textwrap.dedent(f"""
        import sys
        from pathlib import Path
        sys.path.insert(0, {str(ROOT)!r})
        import jax
        assert jax.device_count() == 4, jax.device_count()
        import chip_smoke
        chip_smoke.run_four_chip(Path({str(tmp_path)!r}), n_devices=4, k=8,
                                 seed=0, scale=10, edges=4000,
                                 chunk_edges=512)
        print("FOUR_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR_OK" in out.stdout
    assert "bit-identical to vmap" in out.stdout


def test_script_alone_fails_without_the_repo(tmp_path):
    """Copied into a directory with nothing else of the repo, the script
    exits non-zero and prints no result line."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_text((ROOT / "chip_smoke.py").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, str(lone)], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
