"""Tests for the runtime layer (devices, config, fs, rundir)."""

import dataclasses
import json

import jax
import pytest

from hops_tpu.runtime import config, devices, fs, logging as htlog, rundir


class TestDevices:
    def test_fake_mesh_has_8_chips(self):
        assert devices.get_num_chips() == 8

    def test_topology(self):
        topo = devices.topology()
        assert topo.num_chips == 8
        assert topo.num_hosts == 1
        assert topo.chips_per_host == 8
        assert len(topo.coords) == 8

    def test_mesh_shape_factorization(self):
        topo = devices.topology()
        shape = topo.mesh_shape(2)
        assert shape[0] * shape[1] == 8
        assert shape == (4, 2)

    def test_device_matrix_shape(self):
        m = devices.device_matrix()
        assert m.shape == (1, 8)


class TestConfig:
    def test_defaults_and_configure(self):
        cfg = config.runtime()
        assert cfg.project == "testproj"
        config.configure(seed=42)
        assert config.runtime().seed == 42

    def test_load_from_file_env_overrides(self, tmp_path, monkeypatch):
        @dataclasses.dataclass
        class Train:
            lr: float = 0.1
            steps: int = 10

        @dataclasses.dataclass
        class Cfg:
            name: str = "x"
            train: Train = dataclasses.field(default_factory=Train)

        p = tmp_path / "cfg.json"
        p.write_text(json.dumps({"name": "fromfile", "train": {"lr": 0.5}}))
        monkeypatch.setenv("HOPS_TPU_NAME", "fromenv")
        cfg = config.load(Cfg, path=p, overrides=["train.steps=99"])
        assert cfg.name == "fromenv"  # env beats file
        assert cfg.train.lr == 0.5
        assert cfg.train.steps == 99  # override, coerced to int

    def test_comma_list_override(self):
        @dataclasses.dataclass
        class C:
            mesh: tuple[int, ...] = (1,)
            axes: tuple[str, ...] = ("data",)

        cfg = config.load(C, overrides=["mesh=4,2", "axes=data,model"])
        assert cfg.mesh == (4, 2)
        assert cfg.axes == ("data", "model")

    def test_bool_coercion(self):
        @dataclasses.dataclass
        class C:
            flag: bool = False

        assert config.load(C, overrides=["flag=true"]).flag is True
        assert config.load(C, overrides=["flag=0"]).flag is False

    def test_single_element_tuple_override(self):
        @dataclasses.dataclass
        class C:
            mesh: tuple[int, ...] = (1, 1)

        assert config.load(C, overrides=["mesh=4"]).mesh == (4,)

    def test_optional_and_nested_env(self, monkeypatch):
        @dataclasses.dataclass
        class Inner:
            lr: float = 0.1

        @dataclasses.dataclass
        class C:
            steps: int | None = None
            inner: Inner = dataclasses.field(default_factory=Inner)

        monkeypatch.setenv("HOPS_TPU_STEPS", "5")
        monkeypatch.setenv("HOPS_TPU_INNER", '{"lr": 0.5}')
        cfg = config.load(C)
        assert cfg.steps == 5
        assert cfg.inner.lr == 0.5


class TestFs:
    def test_project_path_scoping(self):
        assert "testproj" in fs.project_path()
        assert fs.project_path("a/b").endswith("testproj/a/b")

    def test_dump_load_roundtrip(self):
        fs.dump("hello", "d/x.txt")
        assert fs.load("d/x.txt") == b"hello"
        fs.dump(b"\x00\x01", "d/y.bin")
        assert fs.load("d/y.bin") == b"\x00\x01"

    def test_mkdir_ls_rmr(self):
        fs.mkdir("sub/dir")
        fs.dump("a", "sub/dir/a.txt")
        assert any(x.endswith("a.txt") for x in fs.ls("sub/dir"))
        fs.rmr("sub")
        assert not fs.exists("sub")

    def test_cp_move_stat(self):
        fs.dump("data", "f1.txt")
        fs.cp("f1.txt", "f2.txt")
        assert fs.load("f2.txt") == b"data"
        fs.move("f2.txt", "f3.txt")
        assert not fs.exists("f2.txt")
        st = fs.stat("f3.txt")
        assert st["size"] == 4 and not st["is_dir"]

    def test_glob(self):
        fs.dump("x", "g/one.csv")
        fs.dump("x", "g/two.csv")
        fs.dump("x", "g/three.txt")
        fs.dump("x", "g/sub/deep.csv")
        hits = fs.glob("g/*.csv")
        assert len(hits) == 2  # * does not cross /
        assert len(fs.glob("g/**/*.csv")) == 3

    def test_copy_to_local_no_overwrite(self, tmp_path):
        fs.dump("v1", "c.txt")
        fs.copy_to_local("c.txt", tmp_path)
        with pytest.raises(FileExistsError):
            fs.copy_to_local("c.txt", tmp_path, overwrite=False)

    def test_copy_to_local_and_back(self, tmp_path):
        fs.dump("payload", "remote.txt")
        local = fs.copy_to_local("remote.txt", tmp_path)
        assert (tmp_path / "remote.txt").read_text() == "payload"
        fs.copy_to_workspace(local, "uploads")
        assert fs.exists("uploads/remote.txt")


class TestRunDir:
    def test_run_ids_increment(self):
        r1 = rundir.new_run()
        r2 = rundir.new_run()
        assert r1.run_id != r2.run_id
        assert r1.run_id.startswith("application_")

    def test_logdir_inside_activation(self):
        run = rundir.new_run()
        with rundir.activate(run):
            assert rundir.logdir() == run.logdir
        assert rundir.logdir() != run.logdir

    def test_activate_chdirs_into_rundir(self):
        import os

        run = rundir.new_run()
        before = os.getcwd()
        with rundir.activate(run):
            assert os.getcwd() == run.logdir
            # relative writes land in the run dir and get synced
            fs.Path("rel.txt").write_text("r")
        assert os.getcwd() == before
        assert (fs.Path(run.finalize()) / "rel.txt").exists()

    def test_local_logdir_sync(self):
        run = rundir.new_run(local_logdir=True)
        with rundir.activate(run):
            (fs.Path(run.logdir) / "model.bin").write_bytes(b"w")
        final = run.finalize()
        assert (fs.Path(final) / "model.bin").read_bytes() == b"w"
        assert "Experiments" in final
        assert run.finalize() == final  # idempotent

    def test_concurrent_activations_are_isolated(self):
        import threading

        results = {}

        def trial(name):
            run = rundir.new_run()
            with rundir.activate(run):
                import time

                time.sleep(0.02)
                results[name] = rundir.logdir() == run.logdir

        threads = [threading.Thread(target=trial, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(results.values())

    def test_session_id_override(self, monkeypatch):
        monkeypatch.setattr(rundir, "_session_id", "application_fixed_1")
        assert rundir.new_run().run_id.startswith("application_fixed_1")


class TestMetricLogger:
    def test_roundtrip(self, tmp_path):
        ml = htlog.MetricLogger(tmp_path / "m.jsonl")
        ml.log(0, "loss", 1.5)
        ml.log(1, "loss", jax.numpy.asarray(0.5))
        ml.close()
        events = htlog.read_metrics(tmp_path / "m.jsonl")
        assert [e["value"] for e in events] == [1.5, 0.5]
