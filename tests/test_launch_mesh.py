"""Launch-layer coverage for the fabric path: mesh-shape resolution and
env overrides (``launch.mesh``) plus the no-device fabric dry-run
(``launch.dryrun``)."""

import importlib
import json
import os
import subprocess
import sys

import jax
import pytest

from repro.launch.mesh import (FABRIC_AXIS, FABRIC_SHARDS_ENV, fabric_mesh,
                               host_device_count_from_flags,
                               maybe_init_distributed,
                               resolve_fabric_shards)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class TestHostDeviceCountFromFlags:
    def test_absent_is_none(self):
        assert host_device_count_from_flags("") is None
        assert host_device_count_from_flags(
            "--xla_cpu_enable_fast_math=true") is None

    def test_present(self):
        assert host_device_count_from_flags(
            "--xla_force_host_platform_device_count=48") == 48
        assert host_device_count_from_flags(
            "-a=1 --xla_force_host_platform_device_count=8 -b=2") == 8

    def test_repeated_flag_last_wins(self):
        flags = ("--xla_force_host_platform_device_count=8 "
                 "--xla_force_host_platform_device_count=48")
        assert host_device_count_from_flags(flags) == 48

    def test_none_reads_environment(self, monkeypatch):
        monkeypatch.setenv(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=17")
        assert host_device_count_from_flags() == 17
        monkeypatch.delenv("XLA_FLAGS")
        assert host_device_count_from_flags() is None


class TestResolveFabricShards:
    def test_explicit_request_wins(self, monkeypatch):
        monkeypatch.setenv(FABRIC_SHARDS_ENV, "9")
        assert resolve_fabric_shards(3) == 3
        assert resolve_fabric_shards(0) == 1          # clamped

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv(FABRIC_SHARDS_ENV, "6")
        assert resolve_fabric_shards() == 6

    def test_default_is_one_per_device(self, monkeypatch):
        monkeypatch.delenv(FABRIC_SHARDS_ENV, raising=False)
        assert resolve_fabric_shards() == max(1, len(jax.devices()))
        assert resolve_fabric_shards(devices=[object()] * 5) == 5


class TestFabricMesh:
    def test_mesh_shape_and_axis(self):
        n = len(jax.devices())
        mesh = fabric_mesh(n)
        assert mesh.axis_names == (FABRIC_AXIS,)
        assert int(mesh.devices.size) == n

    def test_more_shards_than_devices_raises(self):
        n = len(jax.devices())
        with pytest.raises(ValueError, match="xla_force_host_platform"):
            fabric_mesh(n + 1)


class TestMaybeInitDistributed:
    def test_unconfigured_is_false(self, monkeypatch):
        for var in ("REPRO_FABRIC_COORDINATOR",
                    "REPRO_FABRIC_NUM_PROCESSES",
                    "REPRO_FABRIC_PROCESS_ID"):
            monkeypatch.delenv(var, raising=False)
        assert maybe_init_distributed() is False
        # partial configuration is still unconfigured
        monkeypatch.setenv("REPRO_FABRIC_COORDINATOR", "127.0.0.1:9999")
        assert maybe_init_distributed() is False

    def test_failed_initialization_raises(self, monkeypatch):
        """A configured worker whose runtime cannot start must not fall
        back to running alone."""
        def refuse(**kw):
            raise ValueError("coordinator unreachable")

        monkeypatch.setattr(jax.distributed, "is_initialized", lambda: False)
        monkeypatch.setattr(jax.distributed, "initialize", refuse)
        with pytest.raises(ValueError, match="unreachable"):
            maybe_init_distributed("localhost:1", 2, 0)

    def test_already_initialized_is_true(self, monkeypatch):
        def refuse(**kw):
            raise AssertionError("initialized twice")

        monkeypatch.setattr(jax.distributed, "is_initialized", lambda: True)
        monkeypatch.setattr(jax.distributed, "initialize", refuse)
        assert maybe_init_distributed("localhost:1", 2, 0) is True


class TestDryrunFabric:
    def test_import_guard_respects_preset_flags(self, monkeypatch):
        """Reloading ``launch.dryrun`` must not clobber a caller-pinned
        forced-device count (the fabric CI job pins 48), and must append
        the 512 default when none is pinned."""
        import repro.launch.dryrun as dryrun

        preset = "--xla_force_host_platform_device_count=48"
        monkeypatch.setenv("XLA_FLAGS", preset)
        importlib.reload(dryrun)
        assert os.environ["XLA_FLAGS"] == preset
        monkeypatch.setenv("XLA_FLAGS", "--xla_cpu_enable_fast_math=true")
        importlib.reload(dryrun)
        assert host_device_count_from_flags() == 512
        assert "--xla_cpu_enable_fast_math=true" in os.environ["XLA_FLAGS"]

    def test_fabric_dryrun_record(self, tmp_path):
        from repro.launch.dryrun import fabric_dryrun

        rec = fabric_dryrun(tmp_path, n_shards=3, nv=64, ne=200)
        assert rec["ok"] and rec["n_shards"] == 3
        assert rec["n_boxes"] >= 1 and rec["rank"] >= 2
        assert len(rec["shards"]) == 3
        assert sum(s["boxes"] for s in rec["shards"]) == rec["n_boxes"]
        assert sum(s["mass"] for s in rec["shards"]) == rec["total_mass"]
        on_disk = json.loads((tmp_path / "fabric__triangle__s3.json")
                             .read_text())
        assert on_disk == rec

    def test_fabric_cli_smoke(self, tmp_path):
        """``python -m repro.launch.dryrun --fabric`` plans a fabric with
        zero accelerators visible (JAX_PLATFORMS=cpu, 1 device)."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep \
            + env.get("PYTHONPATH", "")
        res = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--fabric",
             "--fabric-shards", "2", "--out", str(tmp_path)],
            capture_output=True, text=True, env=env, timeout=300)
        assert res.returncode == 0, res.stderr[-2000:]
        assert "[OK] fabric__triangle__s2" in res.stdout
        assert (tmp_path / "fabric__triangle__s2.json").exists()


class TestCompileCache:
    FLAGS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")

    @pytest.fixture
    def restore_flags(self):
        saved = {f: getattr(jax.config, f) for f in self.FLAGS}
        yield
        for f, v in saved.items():
            jax.config.update(f, v)

    def test_env_dir_wins_and_nothing_else_is_set(self, monkeypatch,
                                                  restore_flags, tmp_path):
        from repro.launch.compile_cache import enable_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_enable_compilation_cache

    def test_default_is_fixed_checkout_dir(self, monkeypatch,
                                           restore_flags):
        from repro.launch.compile_cache import enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(os.path.dirname(SRC), ".jax_cache")
        path = enable_compile_cache()
        assert os.path.realpath(path) == os.path.realpath(want)
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path     # same dir every call
