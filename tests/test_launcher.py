"""Pod-scale elastic runtime (PR 6): coordinator bootstrap timeout,
membership epochs with a fake clock (heartbeat expiry, join during
recovery, two concurrent leaves), the process-liveness FailureDetector,
the multi-host CheckpointManager write guard, slice-granular
ElasticTrainer recovery over a shrunken dcn mesh, proc_kill/proc_hang
fault determinism, and the PodLauncher's fork/heal/leak-check loop."""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from deeplearning4j_tpu.datasets import DataSet
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.multilayer import (
    MultiLayerNetwork, NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.parallel import (
    CheckpointManager, CoordinatorUnreachableError, ElasticTrainer,
    FailureDetector, FaultKind, FaultSchedule, Heartbeat, HostLostError,
    Membership, MembershipChangedError, PodLauncher, ProcessFailureDetector,
    ShardedTrainer, build_two_tier_mesh, surviving_mesh,
    validate_coordinator_address,
)
from deeplearning4j_tpu.parallel.distributed import (
    ENV_PROCESS_ID, ENV_RUN_DIR, initialize,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# coordinator bootstrap: bounded timeout, no hang (satellite + acceptance)
# ---------------------------------------------------------------------------

class TestCoordinatorBootstrap:
    def test_address_validation(self):
        assert validate_coordinator_address("10.0.0.1:8476") == \
            ("10.0.0.1", 8476)
        assert validate_coordinator_address("[::1]:99") == ("::1", 99)
        for bad in ("nohost", ":1234", "host:", "host:0", "host:70000",
                    "host:port", 12345):
            with pytest.raises(ValueError):
                validate_coordinator_address(bad)

    def test_initialize_rejects_bad_address_up_front(self):
        with pytest.raises(ValueError, match="coordinator_address"):
            initialize("not-an-address", 2, 1)

    def test_initialize_rejects_bad_process_id(self):
        with pytest.raises(ValueError, match="out of range"):
            initialize("127.0.0.1:9999", 2, 5)

    def test_initialize_rejects_bad_timeout(self):
        with pytest.raises(ValueError, match="timeout_s"):
            initialize("127.0.0.1:9999", 2, 1, timeout_s=0)

    def test_dead_coordinator_fails_within_timeout(self):
        """Regression (the indefinite-hang bug): joining a coordinator
        nobody listens on must raise CoordinatorUnreachableError within
        the configured budget, not block forever."""
        import socket
        with socket.socket() as s:      # a port that is definitely dead
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        t0 = time.monotonic()
        with pytest.raises(CoordinatorUnreachableError, match="unreachable"):
            initialize(f"127.0.0.1:{port}", num_processes=2, process_id=1,
                       timeout_s=1.5)
        elapsed = time.monotonic() - t0
        assert elapsed < 15.0, f"took {elapsed:.1f}s — the hang is back"


# ---------------------------------------------------------------------------
# membership transitions (fake clock)
# ---------------------------------------------------------------------------

class TestMembership:
    def test_beat_alive_and_expiry(self, tmp_path):
        clock = FakeClock()
        m = Membership(str(tmp_path), heartbeat_timeout=5.0, clock=clock)
        m.beat(0)
        m.beat(1)
        assert m.alive() == [0, 1]
        clock.t += 6.0
        m.beat(0)                       # only host 0 keeps beating
        assert m.alive() == [0]

    def test_epoch_bumps_once_per_transition_batch(self, tmp_path):
        clock = FakeClock()
        m = Membership(str(tmp_path), heartbeat_timeout=5.0, clock=clock)
        assert m.epoch == 0             # no ledger before the first refresh
        for i in (0, 1, 2):
            m.beat(i)
        assert m.refresh() == 1         # formation
        assert m.refresh() == 1         # no change → no bump
        # two CONCURRENT leaves: both expire in the same scan → ONE bump
        clock.t += 6.0
        m.beat(0)
        assert m.refresh() == 2
        assert m.members() == [0]

    def test_join_during_recovery(self, tmp_path):
        clock = FakeClock()
        m = Membership(str(tmp_path), heartbeat_timeout=5.0, clock=clock)
        m.beat(0)
        m.beat(1)
        m.refresh()
        clock.t += 6.0                  # host 1 dies...
        m.beat(0)
        assert m.refresh() == 2
        m.beat(3)                       # ...and host 3 joins MID-recovery
        assert m.refresh() == 3
        assert m.members() == [0, 3]

    def test_ledger_persists_across_instances(self, tmp_path):
        clock = FakeClock()
        m = Membership(str(tmp_path), heartbeat_timeout=5.0, clock=clock)
        m.beat(0)
        m.refresh()
        m2 = Membership(str(tmp_path), heartbeat_timeout=5.0, clock=clock)
        assert m2.epoch == 1 and m2.members() == [0]

    def test_torn_and_foreign_heartbeat_files_ignored(self, tmp_path):
        m = Membership(str(tmp_path), heartbeat_timeout=5.0)
        (tmp_path / "hb_9.json").write_text("{torn")
        (tmp_path / "hb_x.json").write_text("{}")
        m.beat(2)
        assert m.alive() == [2]

    def test_remove_deregisters(self, tmp_path):
        m = Membership(str(tmp_path), heartbeat_timeout=5.0)
        m.beat(4)
        m.remove(4)
        assert m.alive() == []

    def test_rejects_nonpositive_timeout(self, tmp_path):
        with pytest.raises(ValueError):
            Membership(str(tmp_path), heartbeat_timeout=0)


class TestProcessFailureDetector:
    def _members(self, tmp_path, clock):
        m = Membership(str(tmp_path), heartbeat_timeout=5.0, clock=clock)
        m.beat(0)
        m.beat(1)
        m.refresh()
        return m

    def test_lost_host_raises_recoverable(self, tmp_path):
        clock = FakeClock()
        m = self._members(tmp_path, clock)
        det = ProcessFailureDetector(m)
        det.check()                     # baseline observation
        clock.t += 6.0
        m.beat(0)
        with pytest.raises(HostLostError) as exc:
            det.check()
        assert exc.value.lost == [1]
        assert FailureDetector().is_recoverable(exc.value)
        det.check()                     # transition consumed — no re-raise

    def test_join_raises_membership_changed(self, tmp_path):
        clock = FakeClock()
        m = self._members(tmp_path, clock)
        det = ProcessFailureDetector(m)
        det.check()
        m.beat(2)
        with pytest.raises(MembershipChangedError) as exc:
            det.check()
        assert exc.value.joined == [2]
        assert FailureDetector().is_recoverable(exc.value)

    def test_join_ignored_when_configured(self, tmp_path):
        clock = FakeClock()
        m = self._members(tmp_path, clock)
        det = ProcessFailureDetector(m, recover_on_join=False)
        det.check()
        m.beat(2)
        det.check()                     # no raise


# ---------------------------------------------------------------------------
# multi-host CheckpointManager (satellite)
# ---------------------------------------------------------------------------

class _StubNet:
    def save(self, path, save_updater=True):
        with open(path, "wb") as f:
            f.write(b"stub-checkpoint")


class TestCheckpointManagerMultiHost:
    def test_single_process_default_is_writer(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        assert cm.is_writer and cm.process_id == 0
        assert cm.save(_StubNet(), 3) is not None
        assert len(cm.list_checkpoints()) == 1

    def test_nonzero_process_is_reader_no_tmp_race(self, tmp_path):
        writer = CheckpointManager(str(tmp_path), process_id=0)
        other = CheckpointManager(str(tmp_path), process_id=1)
        assert not other.is_writer
        assert other.save(_StubNet(), 5) is None        # no-op, no .tmp
        assert other.save_async(_StubNet(), 5) is None
        assert os.listdir(tmp_path) == []
        path = writer.save(_StubNet(), 5)
        assert path is not None
        # readers still restore the coordinator's checkpoints (host rejoin)
        model, step = other.restore_latest(lambda p: "loaded")
        assert (model, step) == ("loaded", 5)

    def test_process_id_from_launcher_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv(ENV_PROCESS_ID, "3")
        cm = CheckpointManager(str(tmp_path))
        assert cm.process_id == 3 and not cm.is_writer
        monkeypatch.setenv(ENV_PROCESS_ID, "junk")
        with pytest.raises(ValueError):
            CheckpointManager(str(tmp_path))

    def test_forced_roles(self, tmp_path):
        assert CheckpointManager(str(tmp_path), role="writer",
                                 process_id=7).is_writer
        assert not CheckpointManager(str(tmp_path), role="reader",
                                     process_id=0).is_writer
        with pytest.raises(ValueError):
            CheckpointManager(str(tmp_path), role="bogus")

    def test_per_host_shards_distinct_names(self, tmp_path):
        h0 = CheckpointManager(str(tmp_path), role="per_host", process_id=0)
        h1 = CheckpointManager(str(tmp_path), role="per_host", process_id=1)
        h0.save(_StubNet(), 2)
        h1.save(_StubNet(), 2)          # same step, distinct file — no race
        names = sorted(os.listdir(tmp_path))
        assert names == ["checkpoint_0000000002.h0.zip",
                         "checkpoint_0000000002.h1.zip"]
        # each host lists only its OWN shards; a shared-writer manager
        # ignores per-host shards entirely
        assert [s for _, s in h0.list_checkpoints()] == [2]
        assert h0.list_checkpoints()[0][0].endswith(".h0.zip")
        assert CheckpointManager(str(tmp_path),
                                 process_id=0).list_checkpoints() == []

    def test_stale_tmp_cleanup_respects_ownership(self, tmp_path):
        mine = tmp_path / "checkpoint_0000000001.zip.tmp"
        theirs = tmp_path / "checkpoint_0000000001.h1.zip.tmp"
        mine.write_bytes(b"torn")
        theirs.write_bytes(b"torn")
        CheckpointManager(str(tmp_path), process_id=1)   # reader: cleans nothing
        assert mine.exists() and theirs.exists()
        CheckpointManager(str(tmp_path), process_id=0)   # writer: own names only
        assert not mine.exists() and theirs.exists()
        CheckpointManager(str(tmp_path), role="per_host", process_id=1)
        assert not theirs.exists()


# ---------------------------------------------------------------------------
# slice-granular recovery: host leave → smaller dcn mesh → restore → continue
# ---------------------------------------------------------------------------

def _small_net(seed=5):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(lr=0.1))
            .layer(Dense(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf)
    net.init()
    return net


def _blob_data(n=64):
    rng = np.random.default_rng(0)
    xs = np.concatenate([rng.normal(-2, 1, (n // 2, 4)),
                         rng.normal(2, 1, (n // 2, 4))]).astype(np.float32)
    ys = np.zeros((n, 2), np.float32)
    ys[:n // 2, 0] = 1
    ys[n // 2:, 1] = 1
    return DataSet(xs, ys)


class TestSliceGranularRecovery:
    def test_surviving_mesh_shrinks_dcn(self):
        mesh = surviving_mesh([0], n_slices=2)
        assert dict(mesh.shape)["dcn"] == 1
        assert mesh.devices.size == 4
        import jax
        assert list(mesh.devices.flat) == jax.devices()[:4]
        both = surviving_mesh([0, 1], n_slices=2)
        assert dict(both.shape)["dcn"] == 2 and both.devices.size == 8

    def test_surviving_mesh_validation(self):
        with pytest.raises(ValueError):
            surviving_mesh([], n_slices=2)
        with pytest.raises(ValueError):
            surviving_mesh([2], n_slices=2)
        with pytest.raises(ValueError):
            surviving_mesh([0], n_slices=3)   # 8 devices % 3

    def test_two_tier_trainer_from_megascale_env(self, monkeypatch):
        """ShardedTrainer.two_tier sizes the dcn axis from the multislice
        runtime's env contract (which the launcher propagates)."""
        monkeypatch.setenv("MEGASCALE_NUM_SLICES", "2")
        trainer = ShardedTrainer.two_tier(_small_net())
        assert dict(trainer.mesh.shape) == {"dcn": 2, "data": 4}
        monkeypatch.delenv("MEGASCALE_NUM_SLICES")
        t1 = ShardedTrainer.two_tier(_small_net(), n_slices=1)
        assert dict(t1.mesh.shape) == {"dcn": 1, "data": 8}

    def test_launcher_exports_megascale_env(self, tmp_path):
        env = dict(os.environ)
        env.pop("MEGASCALE_NUM_SLICES", None)
        launcher = PodLauncher(["true"], num_workers=2,
                               run_dir=str(tmp_path), base_env=env,
                               bootstrap="distributed")
        worker_env = launcher._env_for(launcher.handles[1])
        assert worker_env["MEGASCALE_NUM_SLICES"] == "2"
        assert worker_env["DL4J_TPU_COORDINATOR"].startswith("127.0.0.1:")
        replica = PodLauncher(["true"], num_workers=2,
                              run_dir=str(tmp_path), base_env=env,
                              megascale_slices=4)
        assert replica._env_for(replica.handles[0])[
            "MEGASCALE_NUM_SLICES"] == "4"

    def test_host_leave_rebuilds_smaller_mesh_and_continues(self, tmp_path):
        """A lost slice mid-training: the membership check raises
        HostLostError, ElasticTrainer's EXISTING recovery loop (backoff →
        rebuild_fn → restore) re-provisions a dcn=1 mesh over the
        surviving half and training continues from the checkpoint."""
        net = _small_net()
        ds = _blob_data()
        lost = {"pending": None}

        def membership_check():
            if lost["pending"]:
                err = lost["pending"]
                lost["pending"] = None
                raise err

        def rebuild():
            return ShardedTrainer(net, surviving_mesh([0], n_slices=2))

        et = ElasticTrainer(ShardedTrainer(net, build_two_tier_mesh(2)),
                            str(tmp_path), checkpoint_every=2, sync_every=1,
                            rebuild_fn=rebuild,
                            membership_check=membership_check)
        before = [float(et.fit_batch(ds)) for _ in range(4)]
        lost["pending"] = HostLostError([1], epoch=2)
        after = [float(et.fit_batch(ds)) for _ in range(4)]
        assert et.total_restarts == 1
        assert dict(et.trainer.mesh.shape)["dcn"] == 1
        assert et.trainer.mesh.devices.size == 4
        # restored from the step-4 checkpoint and kept learning
        assert after[-1] < before[0]


# ---------------------------------------------------------------------------
# proc_kill / proc_hang faults
# ---------------------------------------------------------------------------

class TestProcessFaults:
    def test_process_kinds_registered(self):
        assert FaultKind.PROC_KILL in FaultKind.ALL
        assert FaultKind.PROC_HANG in FaultKind.ALL
        assert set(FaultKind.PROCESS_KINDS) == {FaultKind.PROC_KILL,
                                                FaultKind.PROC_HANG,
                                                FaultKind.COORD_KILL,
                                                FaultKind.PREEMPT_NOTICE}

    def test_scripted_schedule_accepts_proc_kinds(self):
        s = FaultSchedule.scripted({3: FaultKind.PROC_KILL,
                                    7: [FaultKind.PROC_HANG]})
        assert s.pop(3) == ["proc_kill"]
        assert s.pop(7) == ["proc_hang"]

    def test_random_schedule_with_proc_kinds_is_deterministic(self):
        kinds = list(FaultKind.PROCESS_KINDS)
        a = FaultSchedule.random(seed=11, n_steps=200, rate=0.1, kinds=kinds)
        b = FaultSchedule.random(seed=11, n_steps=200, rate=0.1, kinds=kinds)
        assert a.faults == b.faults and a.pending() > 0
        c = FaultSchedule.random(seed=12, n_steps=200, rate=0.1, kinds=kinds)
        assert a.faults != c.faults

    def test_cli_parse_proc_kinds(self):
        from deeplearning4j_tpu.cli import _parse_chaos
        sched, seed, hang, _slow = _parse_chaos(
            "proc_kill@4,proc_hang@9,seed=2")
        assert sched.faults == {4: ["proc_kill"], 9: ["proc_hang"]}
        assert seed == 2

    def test_proc_kill_self_injects_at_exact_step(self, tmp_path):
        """The fault is step-deterministic: a worker scheduled with
        proc_kill@3 dies by SIGKILL after completing exactly 2 steps —
        every run, no launcher-side polling race."""
        progress = tmp_path / "progress.txt"
        script = textwrap.dedent(f"""
            import os, sys
            os.environ["JAX_PLATFORMS"] = "cpu"
            sys.path.insert(0, {_REPO!r})
            from deeplearning4j_tpu.parallel.chaos import (
                ChaosInjector, FaultKind, FaultSchedule,
            )
            class T:
                net = None
                def fit_batch(self, ds):
                    return 0.0
            inj = ChaosInjector(
                T(), FaultSchedule.scripted({{3: FaultKind.PROC_KILL}}))
            with open({str(progress)!r}, "a") as f:
                for _ in range(5):
                    inj.fit_batch(None)
                    f.write("step\\n")
                    f.flush()
        """)
        p = subprocess.run([sys.executable, "-c", script],
                           capture_output=True, timeout=120)
        assert p.returncode == -9, p.stderr.decode()[-500:]
        assert progress.read_text().count("step") == 2


# ---------------------------------------------------------------------------
# Heartbeat + PodLauncher (stdlib workers — no jax import in children)
# ---------------------------------------------------------------------------

class TestHeartbeat:
    def test_beats_and_stops_clean(self, tmp_path):
        m = Membership(str(tmp_path), heartbeat_timeout=5.0)
        hb = Heartbeat(m, process_id=2, interval=0.02,
                       step_fn=lambda: 7).start()
        time.sleep(0.15)
        rec = m.last_beat(2)
        assert rec is not None and rec["step"] == 7
        thread = hb._thread
        hb.stop()
        assert not thread.is_alive()
        assert m.last_beat(2) is None        # deregistered

    def test_start_from_env(self, tmp_path, monkeypatch):
        monkeypatch.delenv(ENV_RUN_DIR, raising=False)
        assert Heartbeat.start_from_env() is None
        monkeypatch.setenv(ENV_RUN_DIR, str(tmp_path))
        monkeypatch.setenv(ENV_PROCESS_ID, "4")
        hb = Heartbeat.start_from_env(interval=0.02)
        try:
            assert hb is not None
            time.sleep(0.1)
            assert Membership(str(tmp_path)).last_beat(4) is not None
        finally:
            hb.stop()


# a stdlib-only launcher child: beats the Membership heartbeat format by
# hand (the on-disk contract), with failure modes driven by env
_STDLIB_WORKER = textwrap.dedent("""
    import json, os, sys, time
    i = int(os.environ["DL4J_TPU_PROCESS_ID"])
    run = os.environ["DL4J_TPU_RUN_DIR"]
    inc = int(os.environ.get("DL4J_TPU_INCARNATION", "0"))
    mode = os.environ.get("TEST_WORKER_MODE", "ok")
    def beat():
        tmp = os.path.join(run, "hb_%d.json.tmp%d" % (i, os.getpid()))
        with open(tmp, "w") as f:
            json.dump({"process_id": i, "pid": os.getpid(),
                       "step": None, "t": time.time()}, f)
        os.replace(tmp, os.path.join(run, "hb_%d.json" % i))
    if mode == "crash_once" and i == 1 and inc == 0:
        beat(); time.sleep(0.2); sys.exit(3)
    if mode == "hang" and i == 0 and inc == 0:
        beat(); time.sleep(0.3)
        time.sleep(600)            # alive but silent — heartbeat expiry
    for _ in range(8):
        beat(); time.sleep(0.05)
""")


def _stdlib_launcher(tmp_path, mode, **kw):
    env = dict(os.environ)
    env["TEST_WORKER_MODE"] = mode
    defaults = dict(num_workers=2, run_dir=str(tmp_path / "run"),
                    base_env=env, heartbeat_timeout=1.0, max_restarts=2,
                    poll_interval=0.05, deadline_s=60.0)
    defaults.update(kw)
    return PodLauncher([sys.executable, "-c", _STDLIB_WORKER], **defaults)


class TestPodLauncher:
    def test_clean_run_completes_no_leaks(self, tmp_path):
        report = _stdlib_launcher(tmp_path, "ok").run()
        assert report["ok"]
        assert report["completed"] == [0, 1]
        assert report["restarts"] == 0 and report["leaked_killed"] == 0
        assert report["epoch"] >= 1          # formation bumped the ledger

    def test_crash_restarts_worker_as_new_incarnation(self, tmp_path):
        report = _stdlib_launcher(tmp_path, "crash_once").run()
        assert report["ok"] and report["completed"] == [0, 1]
        assert report["restarts"] == 1
        leaves = report["leaves"]
        assert len(leaves) == 1 and leaves[0]["cause"] == "crash" \
            and leaves[0]["rc"] == 3 and leaves[0]["worker"] == 1
        assert report["joins"] == 1
        # the relaunched incarnation got its own log file
        assert (tmp_path / "run" / "logs" / "worker1.inc1.log").exists()

    def test_silent_worker_declared_hung_killed_and_relaunched(self, tmp_path):
        report = _stdlib_launcher(tmp_path, "hang").run()
        assert report["ok"] and report["completed"] == [0, 1]
        assert report["hang_detected"] >= 1
        assert any(e["cause"] == "hang" for e in report["leaves"])
        assert report["restarts"] >= 1 and report["leaked_killed"] == 0

    def test_restart_budget_exhaustion_is_unrecovered(self, tmp_path):
        env = dict(os.environ)
        env["TEST_WORKER_MODE"] = "ok"
        launcher = PodLauncher(
            [sys.executable, "-c", "import sys; sys.exit(4)"],
            num_workers=1, run_dir=str(tmp_path / "run"), base_env=env,
            heartbeat_timeout=1.0, max_restarts=1, poll_interval=0.05,
            deadline_s=30.0)
        report = launcher.run()
        assert not report["ok"] and report["unrecovered"] == [0]
        assert report["restarts"] == 1       # budget spent, then gave up

    def test_chaos_spec_only_reaches_first_incarnation(self, tmp_path):
        probe = textwrap.dedent("""
            import json, os, sys, time
            i = int(os.environ["DL4J_TPU_PROCESS_ID"])
            run = os.environ["DL4J_TPU_RUN_DIR"]
            inc = int(os.environ.get("DL4J_TPU_INCARNATION", "0"))
            spec = os.environ.get("DL4J_TPU_CHAOS")
            with open(os.path.join(run, "spec_%d_%d" % (i, inc)), "w") as f:
                f.write(repr(spec))
            if spec:
                sys.exit(9)    # "the fault fired" — relaunch must be clean
        """)
        launcher = PodLauncher(
            [sys.executable, "-c", probe], num_workers=2,
            run_dir=str(tmp_path / "run"), base_env=dict(os.environ),
            chaos={1: "proc_kill@3"}, heartbeat_timeout=5.0,
            max_restarts=2, poll_interval=0.05, deadline_s=30.0)
        report = launcher.run()
        assert report["ok"] and report["restarts"] == 1
        run = tmp_path / "run"
        assert (run / "spec_0_0").read_text() == "None"
        assert (run / "spec_1_0").read_text() == "'proc_kill@3'"
        assert (run / "spec_1_1").read_text() == "None"

    def test_validation(self, tmp_path):
        with pytest.raises(ValueError):
            PodLauncher(["x"], num_workers=0, run_dir=str(tmp_path))
        with pytest.raises(ValueError):
            PodLauncher(["x"], num_workers=2, run_dir=str(tmp_path),
                        bootstrap="bogus")
        with pytest.raises(ValueError):
            PodLauncher(["x"], num_workers=2, run_dir=str(tmp_path),
                        chaos={5: "proc_kill@1"})


    def test_tpu_host_gives_each_worker_its_own_chips(self, tmp_path,
                                                      monkeypatch):
        """A chip belongs to one process: on a TPU host worker i sees
        only chips [i*K, (i+1)*K), set beside XLA_FLAGS."""
        from deeplearning4j_tpu.parallel import launcher as lmod
        monkeypatch.setattr(lmod, "tpu_chips", lambda: 4)
        tpu_env = dict(os.environ, JAX_PLATFORMS="tpu,cpu")
        one = PodLauncher(["x"], num_workers=4, run_dir=str(tmp_path),
                          base_env=tpu_env)
        envs = [one._env_for(h) for h in one.handles]
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
        assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
        assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
        two = PodLauncher(["x"], num_workers=2, run_dir=str(tmp_path),
                          base_env=tpu_env, devices_per_worker=2)
        e1 = two._env_for(two.handles[1])
        assert e1["TPU_VISIBLE_CHIPS"] == "2,3"
        assert e1["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
        # a lone worker with no explicit count keeps the whole host
        solo = PodLauncher(["x"], num_workers=1, run_dir=str(tmp_path),
                           base_env=tpu_env)
        assert "TPU_VISIBLE_CHIPS" not in solo._env_for(solo.handles[0])
        # CPU-pinned workers on the same host need no chip and get none
        cpu = PodLauncher(["x"], num_workers=8, run_dir=str(tmp_path),
                          base_env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert "TPU_VISIBLE_CHIPS" not in cpu._env_for(cpu.handles[0])

    def test_launch_parent_stays_off_the_backend(self):
        """Everything `launch` runs before it forks — the imports, the
        compile-cache set-up, building the launcher — must leave JAX's
        backends uninitialised: on a TPU host a parent that touched a
        backend holds every chip its workers need."""
        import subprocess
        import sys
        code = (
            "import sys; sys.path.insert(0, sys.argv[1])\n"
            "from jax._src import xla_bridge\n"
            "from deeplearning4j_tpu import cli\n"
            "from deeplearning4j_tpu.parallel.launcher import PodLauncher\n"
            "from deeplearning4j_tpu.serving.warmcache import "
            "enable_compile_cache\n"
            "enable_compile_cache()\n"
            "PodLauncher(['x'], num_workers=2, run_dir=sys.argv[2])\n"
            "assert not xla_bridge.backends_are_initialized()\n")
        import tempfile
        with tempfile.TemporaryDirectory() as d:
            p = subprocess.run([sys.executable, "-c", code, _REPO, d],
                               capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]

    def test_tpu_host_refuses_at_once_what_would_hang(self, tmp_path,
                                                      monkeypatch):
        from deeplearning4j_tpu.parallel import launcher as lmod
        monkeypatch.setattr(lmod, "tpu_chips", lambda: 1)
        tpu_env = dict(os.environ, JAX_PLATFORMS="tpu,cpu")
        with pytest.raises(ValueError, match="one process at a time"):
            PodLauncher(["x"], num_workers=2, run_dir=str(tmp_path),
                        base_env=tpu_env)
        monkeypatch.setattr(lmod, "tpu_chips", lambda: 4)
        with pytest.raises(ValueError, match="distributed"):
            PodLauncher(["x"], num_workers=2, run_dir=str(tmp_path),
                        base_env=tpu_env, bootstrap="distributed")
        with pytest.raises(ValueError, match="chips"):
            PodLauncher(["x"], num_workers=1, run_dir=str(tmp_path),
                        base_env=tpu_env, devices_per_worker=3)

# ---------------------------------------------------------------------------
# the process-scale soak itself (quick mode; heavier → slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestMultiprocSoak:
    def test_quick_multiproc_soak_all_gates(self, tmp_path):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chaos_soak", os.path.join(_REPO, "scripts", "chaos_soak.py"))
        soak = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(soak)
        out = soak.run_multiproc_soak(quick=True, root=str(tmp_path))
        assert out["unrecovered"] == 0
        assert out["off_bitwise"], "launcher machinery changed the math"
        assert out["proc_kill_recovered"] >= 1
        assert out["proc_hang_recovered"] >= 1
        assert out["chaos_loss_bitwise"], \
            "post-resume trajectory diverged from baseline"
        assert out["leaked"] == 0 and out["off_leaked"] == 0
        assert out["writer_guard_ok"] and out["completion_steps_ok"]
        assert out["soak_ok"], out


class TestInjectableLauncherClock:
    def test_pod_launcher_shares_one_injected_clock(self, tmp_path):
        """GC201 regression (graftcheck): launcher event times, notice
        deadlines and heartbeat staleness all read ONE injectable clock
        (shared with the Membership ledger) instead of raw time.time()."""
        t = [5000.0]
        launcher = PodLauncher(["true"], num_workers=1,
                               run_dir=str(tmp_path),
                               clock=lambda: t[0])
        assert launcher.clock() == 5000.0
        assert launcher.membership.clock is launcher.clock
        launcher._t0 = launcher.clock()
        t[0] = 5001.5
        launcher._event("probe", 0)
        assert launcher.events[-1]["t"] == 1.5
