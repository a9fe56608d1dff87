"""``chip_smoke.py``'s phases, rehearsed on the CPU at small shapes.

The script refuses to run without a TPU, but its phases take the
dispatch table and the tier every kernel site must resolve to. Here they
run the Pallas kernels in the interpreter (``dispatch="interpret"``), so
the script's own comparisons, tier checks and sharded-against-one-device
logic are exercised without a chip.
"""

from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
SMALL_GRAPH = {"nodes": 200, "edges": 800, "feat": 8, "classes": 4, "hidden": 16}
SMALL_LOGREG = {"n": 256, "m": 16}


@pytest.fixture(scope="module")
def smoke():
    # Other test modules turn on x64 as they are imported; the script
    # runs without it.
    saved = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    yield mod
    jax.config.update("jax_enable_x64", saved)


def test_logreg_phase_passes_on_the_interpret_tier(smoke):
    assert smoke.logreg_phase(
        seed=0, dispatch="interpret", tier="interpret", **SMALL_LOGREG
    ) == []


def test_gcn_phase_passes_on_the_interpret_tier(smoke):
    assert smoke.gcn_phase(
        seed=0, dispatch="interpret", tier="interpret", **SMALL_GRAPH
    ) == []


def test_phase_fails_when_a_site_misses_the_expected_tier(smoke):
    bad = smoke.logreg_phase(seed=0, dispatch="jnp", tier="pallas", **SMALL_LOGREG)
    assert bad and all(b.startswith("logreg:blocked_matmul[") for b in bad), bad


def test_check_tiers_fails_a_site_that_resolved_to_two_tiers(smoke):
    sites = {"segment_sum[E=8,D=4,S=2]": ("jnp", "pallas"),
             "gather_join[E=8,N=2,D=4]": ("pallas",)}
    assert smoke._check_tiers("p", sites, "pallas") == [
        "p:segment_sum[E=8,D=4,S=2]=jnp/pallas"
    ]


def _env(**extra):
    return {
        "PYTHONPATH": str(REPO / "src"),
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "JAX_PLATFORMS": "cpu",
        **extra,
    }


def test_script_exits_nonzero_without_a_tpu():
    r = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, env=_env(), cwd=str(REPO), timeout=300,
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


_SHARDED_SCRIPT = textwrap.dedent(
    """
    import importlib.util, json, sys
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    bad = smoke.sharded_phase(
        4, 0, arxiv=json.loads(sys.argv[2]), logreg=json.loads(sys.argv[3]),
        dispatch="interpret", tier="interpret",
    )
    assert bad == [], bad
    print("SHARDED-OK")
    """
)


@pytest.mark.spmd
def test_sharded_phase_matches_one_device_on_four_cpu_devices():
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT, str(REPO / "chip_smoke.py"),
         json.dumps(SMALL_GRAPH), json.dumps(SMALL_LOGREG)],
        capture_output=True, text=True, cwd=str(REPO), timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
    )
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "SHARDED-OK" in r.stdout
