"""``chip_smoke.py`` rehearsed on the CPU backend.

The smoke's logic (``repro.launch.smoke.run``) runs here at a small size
— large enough that the planner picks the sparse path on its own — with
only the chip-only checks off.  The script itself must refuse a CPU
backend, and the compile cache it turns on must land where the
environment or the checkout says.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.launch import cache, smoke

REPO_ROOT = cache.CHECKOUT


def test_smoke_rehearsal_on_cpu():
    rep = smoke.run(12_000, 12_000, on_chip=False, log=lambda _: None)
    assert set(rep["queries"]) == {"count", "count_sum", "minmax", "fused"}
    assert rep["queries"]["fused"]["dispatches"] == {"fused": 4}
    assert all("warm_s" in q for q in rep["queries"].values())


def test_smoke_catches_a_wrong_answer():
    db = smoke.smoke_database(2_000)
    q = smoke.smoke_queries()["count_sum"].engine("tensor")
    want = q.plan(db).execute()
    got = q.plan(db).execute()
    got.relation.columns["qty"] = got.column("qty") + 1.0
    smoke.check_equal("same", want, want)
    with pytest.raises(smoke.SmokeFailure, match="'qty' differs"):
        smoke.check_equal("count_sum", got, want)


def _chip_smoke_module():
    sys.path.insert(0, str(REPO_ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO_ROOT))
    return chip_smoke


def test_fused_reach_on_the_tpch_chain():
    """The fused hop keeps whole child messages resident in VMEM, so on
    the TPC-H chain at its real widths (161 zipcodes at N_SMALL) it runs
    the smoke's k=1 COUNT on every hop, refuses a k=5 bundle on the
    lineitem and orders hops with an error naming the hop and the bound,
    and refuses even k=1 one size above N_SMALL.  The refusal comes from
    the resident message, not the edge tile: a 256-edge tile would not
    fit either."""
    from repro.kernels import autotune, fused_hop

    n_small = _chip_smoke_module().N_SMALL

    def configs(n, k):
        plan = smoke.smoke_queries()["fused"].plan(smoke.smoke_database(n))
        return {
            e["rel"]: e for e in autotune.plan_kernel_configs(plan.prep, k=k)
        }

    assert all(e["config"] is not None for e in configs(n_small, 1).values())
    k5 = configs(n_small, 5)
    refused = {rel for rel, e in k5.items() if e["config"] is None}
    assert refused == {"lineitem", "orders"}
    for rel in refused:
        shape = k5[rel]["shape"]
        assert shape.child_widths == (161,)
        assert f"bound {fused_hop.VMEM_BYTES} B" in k5[rel]["no_fit"]
        # a 256-edge tile (below the 1024 edge granule) would not fit either
        least = min(
            fused_hop.fused_hop_vmem_bytes(
                256, bs, br, shape.child_rows, shape.child_widths,
                shape.width, 5,
            )
            for bs in (64, 128, 256) for br in (128, 256)
        )
        assert least > fused_hop.VMEM_BYTES
        with pytest.raises(ValueError, match=r"fused_hop\|.*\|k5\|sum"):
            autotune.model_tiles_for(shape)
    assert configs(2 * n_small, 1)["lineitem"]["config"] is None


def test_chip_smoke_refuses_cpu(capsys):
    chip_smoke = _chip_smoke_module()
    assert chip_smoke.main() == 1
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "needs a TPU" in err


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(tmp_path, from_env):
    """Runs in a child process: tests never turn the cache on in the
    test process itself."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = str(REPO_ROOT / "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jc")
    script = (
        "import json, jax\n"
        "from repro.launch.cache import enable_compile_cache\n"
        "d = enable_compile_cache()\n"
        "print(json.dumps({'dir': d, 'min': "
        "jax.config.jax_persistent_cache_min_compile_time_secs}))\n"
    )
    res = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=120, cwd=tmp_path,
    )
    assert res.returncode == 0, res.stderr
    got = json.loads(res.stdout.strip().splitlines()[-1])
    want = tmp_path / "jc" if from_env else REPO_ROOT / ".cache" / "jax"
    assert got == {"dir": str(want), "min": 0}
