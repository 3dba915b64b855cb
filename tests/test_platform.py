"""Platform choice and the one compile cache (PR 21).

JAX_PLATFORMS=cpu is the only way to choose the CPU: nothing probes
the backend or falls back to the CPU on its own, so chip_smoke.py on a
machine without a chip fails instead of reporting a result. JAX's
persistent compilation cache has one home: JAX_COMPILATION_CACHE_DIR
when set, else the fixed <checkout>/.jax_cache.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")
    }
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}, **extra)
    return env


def _cache_dir_in_child(**extra) -> str:
    out = subprocess.run(
        [
            sys.executable, "-c",
            "from open_simulator_tpu.utils.compile_cache import "
            "configure_compile_cache; print(configure_compile_cache())",
        ],
        env=_env(**extra), capture_output=True, text=True, timeout=120,
        check=True,
    )
    return out.stdout.strip().splitlines()[-1]


def test_cache_env_var_wins(tmp_path):
    assert _cache_dir_in_child(
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "x")
    ) == str(tmp_path / "x")


def test_cache_default_is_fixed_inside_the_checkout():
    from open_simulator_tpu.utils.compile_cache import DEFAULT_CACHE_DIR

    assert DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert _cache_dir_in_child() == DEFAULT_CACHE_DIR


def test_store_fallback_leaves_a_set_cache_dir_alone(tmp_path, monkeypatch):
    import jax

    from open_simulator_tpu.incremental.store import MODE_ENV, ArtifactStore

    knobs = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    saved = {k: getattr(jax.config, k) for k in knobs}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "set"))
        monkeypatch.setenv(MODE_ENV, "cache")  # force the fallback now
        store = ArtifactStore(str(tmp_path / "store"))
        assert store.stats()["fallback"] is True
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "set")
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_apply_writes_its_cache_under_the_env_dir(tmp_path):
    cache = tmp_path / "x"
    proc = subprocess.run(
        [
            sys.executable, "-m", "open_simulator_tpu.cli", "apply",
            "-f", "example/simon-config.yaml", "--format", "json",
        ],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env=_env(
            JAX_COMPILATION_CACHE_DIR=str(cache),
            JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        ),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout)["success"] is True
    assert any(name.endswith("-cache") for name in os.listdir(cache))


def test_chip_smoke_fails_without_a_chip():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok": true' not in proc.stdout


@pytest.mark.parametrize(
    "platforms, replicas, refused",
    [("", 2, True), ("", 1, False), ("cpu", 4, False), ("tpu", 2, True)],
)
def test_fleet_refuses_replicas_sharing_chips(
    monkeypatch, platforms, replicas, refused
):
    from jax._src import hardware_utils

    from open_simulator_tpu.fleet.replica import check_replica_count
    from open_simulator_tpu.models.validation import InputError

    # a TPU host as the PCI bus shows it; the check never touches JAX's
    # backend (the supervisor must not take the chip itself)
    monkeypatch.setattr(
        hardware_utils, "num_available_tpu_chips_and_device_id",
        lambda: (4, None),
    )
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    if refused:
        with pytest.raises(InputError, match="one replica"):
            check_replica_count(replicas)
    else:
        check_replica_count(replicas)


def test_fleet_cli_refusal_is_exit_2(monkeypatch, tmp_path, capsys):
    from jax._src import hardware_utils

    from open_simulator_tpu.cli import main

    monkeypatch.setattr(
        hardware_utils, "num_available_tpu_chips_and_device_id",
        lambda: (1, None),
    )
    monkeypatch.delenv("JAX_PLATFORMS")
    rc = main([
        "fleet", "-f", os.path.join(REPO, "example/simon-config.yaml"),
        "--replicas", "2", "--fleet-dir", str(tmp_path / "fleet"),
    ])
    assert rc == 2
    assert "TPU host" in capsys.readouterr().err
    assert not (tmp_path / "fleet").exists()
