"""Launcher tests (mirrors reference tests/unit/test_run.py:6-91 plus the per-node
rank-mapping/env logic that the reference left untested)."""

import base64
import json

import pytest

from deepspeed_tpu.launcher import runner as dsrun
from deepspeed_tpu.launcher.launch import build_rank_mapping, child_env


def test_parser_mutual_exclusion():
    """cannot specify both include and exclude (reference test_run.py:6)."""
    with pytest.raises(ValueError):
        dsrun.parse_resource_filter({}, include_str="1", exclude_str="1")


def test_num_plus_filter_rejected():
    with pytest.raises(ValueError):
        dsrun.main(args="--num_nodes 1 --include worker-0 foo.py".split())
    with pytest.raises(ValueError):
        dsrun.main(args="--num_gpus 1 --exclude worker-0:0 foo.py".split())


def test_hostfile_parse(tmp_path):
    hostfile = tmp_path / "hostfile"
    hostfile.write_text("worker-0 slots=4\nworker-1 slots=4\n\n# comment\n")
    pool = dsrun.fetch_hostfile(str(hostfile))
    assert list(pool.items()) == [("worker-0", 4), ("worker-1", 4)]


def test_hostfile_duplicate_rejected(tmp_path):
    hostfile = tmp_path / "hostfile"
    hostfile.write_text("worker-0 slots=4\nworker-0 slots=2\n")
    with pytest.raises(ValueError):
        dsrun.fetch_hostfile(str(hostfile))


def test_hostfile_bad_format(tmp_path):
    hostfile = tmp_path / "hostfile"
    hostfile.write_text("worker-0 4\n")
    with pytest.raises(ValueError):
        dsrun.fetch_hostfile(str(hostfile))


def test_hostfile_missing():
    assert dsrun.fetch_hostfile("/definitely/not/a/hostfile") is None


@pytest.fixture
def two_workers():
    return {"worker-0": [0, 1, 2, 3], "worker-1": [0, 1, 2, 3]}


def test_include_whole_host(two_workers):
    out = dsrun.parse_resource_filter(two_workers, include_str="worker-1")
    assert out == {"worker-1": [0, 1, 2, 3]}


def test_include_slots(two_workers):
    out = dsrun.parse_resource_filter(two_workers, include_str="worker-0@worker-1:0,2")
    assert out == {"worker-0": [0, 1, 2, 3], "worker-1": [0, 2]}


def test_exclude_slots(two_workers):
    out = dsrun.parse_resource_filter(two_workers, exclude_str="worker-1:0")
    assert out == {"worker-0": [0, 1, 2, 3], "worker-1": [1, 2, 3]}


def test_exclude_whole_host(two_workers):
    out = dsrun.parse_resource_filter(two_workers, exclude_str="worker-1")
    assert out == {"worker-0": [0, 1, 2, 3]}


def test_exclude_all_slots_drops_host(two_workers):
    out = dsrun.parse_resource_filter(two_workers, exclude_str="worker-0:0,1,2,3")
    assert out == {"worker-1": [0, 1, 2, 3]}


def test_filter_unknown_host(two_workers):
    with pytest.raises(ValueError):
        dsrun.parse_resource_filter(two_workers, include_str="worker-7")
    with pytest.raises(ValueError):
        dsrun.parse_resource_filter(two_workers, exclude_str="worker-0:9")


def test_filter_preserves_order(two_workers):
    out = dsrun.parse_resource_filter(two_workers, include_str="worker-1@worker-0:1")
    assert list(out.keys()) == ["worker-0", "worker-1"]


def test_world_info_roundtrip(two_workers):
    encoded = dsrun.encode_world_info(two_workers)
    assert dsrun.decode_world_info(encoded) == two_workers
    # urlsafe alphabet only (no +, /, spaces) — must survive as one shell token
    assert set(encoded) <= set("ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789-_=")
    json.loads(base64.urlsafe_b64decode(encoded))


def test_rank_mapping():
    world = {"worker-0": [0, 1], "worker-1": [0, 1], "worker-2": [0]}
    mapping, world_size = build_rank_mapping(world)
    assert world_size == 5
    assert mapping == {"worker-0": [0, 1], "worker-1": [2, 3], "worker-2": [4]}


def test_child_env_multi_proc_per_host():
    world = {"worker-0": [0, 1], "worker-1": [0, 1]}
    env = child_env({}, world, node_rank=1, local_rank=1, master_addr="10.0.0.1", master_port=29500)
    assert env["RANK"] == "3" and env["WORLD_SIZE"] == "4" and env["LOCAL_RANK"] == "1"
    assert env["DS_COORDINATOR_ADDRESS"] == "10.0.0.1:29500"
    assert env["DS_PROCESS_ID"] == "3" and env["DS_NUM_PROCESSES"] == "4"
    assert env["TPU_VISIBLE_DEVICES"] == "1"
    # libtpu topology: distinct per-process port, full address list, task id
    env0 = child_env({}, world, node_rank=1, local_rank=0, master_addr="10.0.0.1", master_port=29500)
    assert env["TPU_PROCESS_PORT"] != env0["TPU_PROCESS_PORT"]
    assert env["TPU_PROCESS_ADDRESSES"] == "worker-0:8476,worker-0:8477,worker-1:8476,worker-1:8477"
    assert env["CLOUD_TPU_TASK_ID"] == "3"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,4"


def test_num_gpus_exceeding_slots_rejected(tmp_path, monkeypatch):
    hostfile = tmp_path / "hostfile"
    hostfile.write_text("worker-0 slots=2\n")
    monkeypatch.setattr(dsrun.subprocess, "check_output", lambda *a, **k: b"10.0.0.1 ")
    with pytest.raises(ValueError, match="exceeds"):
        dsrun.main(args=["--hostfile", str(hostfile), "--num_gpus", "4", "train.py"])


def test_mpi_env_identity_variants(monkeypatch):
    from deepspeed_tpu.runtime import dist as ds_dist
    for k in ["DS_COORDINATOR_ADDRESS", "DS_NUM_PROCESSES", "DS_PROCESS_ID", "MASTER_ADDR",
              "WORLD_SIZE", "RANK", "OMPI_COMM_WORLD_SIZE", "MV2_COMM_WORLD_SIZE", "PMI_SIZE"]:
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("DS_COORDINATOR_ADDRESS", "h0:29500")
    monkeypatch.setenv("MV2_COMM_WORLD_SIZE", "4")
    monkeypatch.setenv("MV2_COMM_WORLD_RANK", "1")
    assert ds_dist._env_identity() == ("h0:29500", 4, 1)
    monkeypatch.delenv("MV2_COMM_WORLD_SIZE")
    monkeypatch.setenv("PMI_SIZE", "2")
    monkeypatch.setenv("PMI_RANK", "0")
    assert ds_dist._env_identity() == ("h0:29500", 2, 0)


def test_child_env_one_proc_per_host():
    """slots=1 per host: the process owns every local chip — no pinning env."""
    world = {"worker-0": [0], "worker-1": [0]}
    env = child_env({"HOME": "/root"}, world, node_rank=0, local_rank=0,
                    master_addr="10.0.0.1", master_port=1234)
    assert env["RANK"] == "0" and env["WORLD_SIZE"] == "2"
    assert "TPU_VISIBLE_DEVICES" not in env
    assert env["HOME"] == "/root"


def test_env_identity_parsing(monkeypatch):
    from deepspeed_tpu.runtime import dist as ds_dist
    for k in ["DS_COORDINATOR_ADDRESS", "DS_NUM_PROCESSES", "DS_PROCESS_ID",
              "MASTER_ADDR", "MASTER_PORT", "WORLD_SIZE", "RANK", "OMPI_COMM_WORLD_SIZE"]:
        monkeypatch.delenv(k, raising=False)
    assert ds_dist._env_identity() is None
    monkeypatch.setenv("MASTER_ADDR", "host0")
    monkeypatch.setenv("MASTER_PORT", "1111")
    monkeypatch.setenv("WORLD_SIZE", "8")
    monkeypatch.setenv("RANK", "5")
    assert ds_dist._env_identity() == ("host0:1111", 8, 5)
    monkeypatch.setenv("DS_COORDINATOR_ADDRESS", "host9:2222")
    monkeypatch.setenv("DS_NUM_PROCESSES", "4")
    monkeypatch.setenv("DS_PROCESS_ID", "2")
    assert ds_dist._env_identity() == ("host9:2222", 4, 2)


def test_init_distributed_noop_single_process(monkeypatch):
    from deepspeed_tpu.runtime import dist as ds_dist
    for k in ["DS_COORDINATOR_ADDRESS", "DS_NUM_PROCESSES", "DS_PROCESS_ID",
              "MASTER_ADDR", "WORLD_SIZE", "RANK", "OMPI_COMM_WORLD_SIZE"]:
        monkeypatch.delenv(k, raising=False)
    assert ds_dist.init_distributed() is False


def test_single_node_cmd(tmp_path, monkeypatch):
    """single-host path builds a launch.py exec line (reference runner.py:309-319)."""
    captured = {}

    class FakeProc:
        returncode = 0
        def wait(self):
            return 0

    def fake_popen(cmd, env=None):
        captured["cmd"] = cmd
        return FakeProc()

    monkeypatch.setattr(dsrun.subprocess, "Popen", fake_popen)
    monkeypatch.setenv("DS_NUM_CHIPS", "4")
    with pytest.raises(SystemExit):
        dsrun.main(args=["--hostfile", "/nope", "train.py", "--foo", "1"])
    cmd = captured["cmd"]
    assert "deepspeed_tpu.launcher.launch" in cmd
    assert cmd[-3:] == ["train.py", "--foo", "1"]
    world_arg = [c for c in cmd if c.startswith("--world_info=")][0]
    world = dsrun.decode_world_info(world_arg.split("=", 1)[1])
    assert world == {"localhost": [0, 1, 2, 3]}


def test_pdsh_cmd_construction(tmp_path):
    args = dsrun.parse_args(["--hostfile", "/nope", "--master_addr", "10.0.0.1",
                             "train.py", "--epochs", "2"])
    from deepspeed_tpu.launcher.multinode_runner import PDSHRunner
    r = PDSHRunner(args, world_info_base64="V0lORk8=")
    r.add_export("XLA_FLAGS", "--xla_foo")
    cmd = r.get_cmd({}, {"worker-0": [0], "worker-1": [0]})
    joined = " ".join(cmd)
    assert cmd[0] == "pdsh"
    assert "-w worker-0,worker-1" in joined
    assert "export XLA_FLAGS=--xla_foo;" in joined
    assert "--node_rank=%n" in joined
    assert "deepspeed_tpu.launcher.launch" in joined
    assert "'2'" in joined  # non-flag user args quoted


def test_num_gpus_without_hostfile_honored(monkeypatch):
    """localhost slot count is a heuristic → --num_gpus overrides it."""
    captured = {}

    class FakeProc:
        returncode = 0
        def wait(self):
            return 0

    monkeypatch.setattr(dsrun.subprocess, "Popen",
                        lambda cmd, env=None: captured.update(cmd=cmd) or FakeProc())
    monkeypatch.delenv("DS_NUM_CHIPS", raising=False)
    with pytest.raises(SystemExit):
        dsrun.main(args=["--hostfile", "/nope", "--num_gpus", "4", "train.py"])
    world_arg = [c for c in captured["cmd"] if c.startswith("--world_info=")][0]
    assert dsrun.decode_world_info(world_arg.split("=", 1)[1]) == {"localhost": [0, 1, 2, 3]}


# ---------------------------------------------------------------------------
# Real multi-process integration: spawn 2 jax.distributed CPU processes through
# launcher/launch.py and assert loss parity with a single-process run over the
# same 2-device mesh (reference strategy: tests/unit/common.py:14-100).
# ---------------------------------------------------------------------------

import os
import subprocess
import sys

import numpy as np


# single source of truth for spawn-env scrubbing + port pick (shared with the
# dry-run rehearsal)
from launcher_worker import clean_spawn_env as _clean_env, free_port as _free_port  # noqa: E402


def test_two_process_launcher_loss_parity(tmp_path):
    worker = os.path.join(os.path.dirname(__file__), "launcher_worker.py")

    # 2 real processes (1 CPU device each) through the per-node launcher
    out_multi = tmp_path / "multi.json"
    world_info = base64.urlsafe_b64encode(
        json.dumps({"localhost": [0, 1]}).encode()).decode()
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    cmd = [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
           "--node_rank=0", "--master_addr=127.0.0.1",
           f"--master_port={_free_port()}", f"--world_info={world_info}",
           worker, f"--out={out_multi}", "--steps=3"]
    proc = subprocess.run(cmd, env=_clean_env(PYTHONPATH=repo_root),
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, f"launcher failed:\n{proc.stdout}\n{proc.stderr}"
    multi = json.loads(out_multi.read_text())
    assert multi["world"] == 2 and multi["devices"] == 2, multi

    # single process over a forced 2-device mesh: same global math
    out_single = tmp_path / "single.json"
    proc = subprocess.run(
        [sys.executable, worker, f"--out={out_single}", "--steps=3"],
        env=_clean_env(XLA_FLAGS="--xla_force_host_platform_device_count=2"),
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, f"single-process run failed:\n{proc.stderr}"
    single = json.loads(out_single.read_text())
    assert single["world"] == 1 and single["devices"] == 2, single

    np.testing.assert_allclose(multi["losses"], single["losses"], rtol=1e-5, atol=1e-6)


def test_mpi_identity_without_coordinator(tmp_path):
    """MPI env without DS_COORDINATOR_ADDRESS negotiates the address over mpi4py
    (reference engine.py:198-235) or fails with an actionable error when mpi4py
    is absent — never silently proceeds with a wrong identity. Probed in a
    subprocess: initializing a real MPI (when present) inside the shared pytest
    process could abort or wedge the whole session."""
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    probe = (
        "import sys\n"
        "from deepspeed_tpu.runtime import dist as ds_dist\n"
        "try:\n"
        "    coord, nprocs, pid = ds_dist._env_identity()\n"
        "    assert nprocs == 2 and pid == 0 and ':' in coord, (coord, nprocs, pid)\n"
        "    print('NEGOTIATED')\n"
        "except RuntimeError as e:\n"
        "    assert 'mpi4py' in str(e), e\n"
        "    print('ACTIONABLE-ERROR')\n"
    )
    env = _clean_env(PYTHONPATH=repo_root, OMPI_COMM_WORLD_SIZE="2",
                     OMPI_COMM_WORLD_RANK="0")
    r = subprocess.run([sys.executable, "-c", probe], env=env, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() in ("NEGOTIATED", "ACTIONABLE-ERROR"), r.stdout

    # single-rank mpirun must NOT raise: there is no world to join
    probe1 = (
        "from deepspeed_tpu.runtime import dist as ds_dist\n"
        "assert ds_dist.init_distributed() is False\n"
        "print('SINGLE-OK')\n"
    )
    env = _clean_env(PYTHONPATH=repo_root, OMPI_COMM_WORLD_SIZE="1",
                     OMPI_COMM_WORLD_RANK="0")
    r = subprocess.run([sys.executable, "-c", probe1], env=env, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "SINGLE-OK" in r.stdout


def test_two_process_offload_elastic_world_change(tmp_path):
    """The sharded-state LIFECYCLE across a world-size change:
    2 real jax.distributed processes train ZeRO-2+offload and save per-process
    region files; a FRESH single-process engine (2 virtual devices — same global
    math) elastically reloads the 2-process checkpoint (merge + re-scatter) and
    continues training; the continued losses must equal an uninterrupted
    single-process run, step for step. Mirrors the reference's
    elastic-dp-change reload (stage2.py:1713-1779, engine.py:1365-1374)."""
    from launcher_worker import run_elastic_rehearsal
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    run_elastic_rehearsal(str(tmp_path), repo_root)


def test_two_process_hierarchical_comm_loss_parity(tmp_path):
    """Two-level ICI+DCN comm across REAL process boundaries: 2 launcher-spawned
    jax.distributed processes x 2 virtual devices (dp 4, auto-factorized 2x2 —
    the DCN boundary IS the process boundary) train ZeRO-2 hierarchical and
    OneBitAdam hierarchical_compressed; losses must match single-process flat
    oracles over the same 4-device global math (exact-mean tolerance for
    hierarchical and the OneBit warmup, documented 1-bit tolerance after the
    freeze step). Shares the implementation with __graft_entry__'s dry run."""
    from launcher_worker import run_hierarchical_rehearsal
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    run_hierarchical_rehearsal(str(tmp_path), repo_root)


def test_two_process_cluster_observatory(tmp_path):
    """Cluster observatory across REAL process boundaries (docs/cluster.md):
    2 launcher-spawned jax.distributed processes with ``telemetry.cluster``
    enabled. An injected 150 ms/step sleep on rank 1 must be NAMED as the
    straggler by rank 0's heartbeat aggregation (exercises the host-local
    dispatch column — the end-to-end wall is collective-equalised and can't
    attribute), and an injected 2 s stall against a 0.5 s hang deadline must
    produce flight-recorder dumps on BOTH hosts that ``cluster-dump``
    assembles into one report naming a stalled host and its scope. Shares
    the implementation with __graft_entry__'s dry run."""
    from launcher_worker import run_cluster_observatory_rehearsal
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    run_cluster_observatory_rehearsal(str(tmp_path), repo_root)


def test_two_process_offload_region_checkpoint(tmp_path):
    """Multi-host ZeRO-Offload end-to-end: 2 real jax.distributed processes train with
    partitioned host-tier Adam, each writes ITS OWN region file on save, and a fresh
    2-process engine reloads bit-identical local buffers (the multi-host analog of the
    reference's per-rank zero_pp checkpoint files)."""
    worker = os.path.join(os.path.dirname(__file__), "launcher_worker.py")
    out = tmp_path / "offload.json"
    ckpt = tmp_path / "ckpt"
    world_info = base64.urlsafe_b64encode(
        json.dumps({"localhost": [0, 1]}).encode()).decode()
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    cmd = [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
           "--node_rank=0", "--master_addr=127.0.0.1",
           f"--master_port={_free_port()}", f"--world_info={world_info}",
           worker, f"--out={out}", "--steps=3", "--offload", f"--ckpt_dir={ckpt}"]
    proc = subprocess.run(cmd, env=_clean_env(PYTHONPATH=repo_root),
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, f"launcher failed:\n{proc.stdout}\n{proc.stderr}"
    result = json.loads(out.read_text())
    assert result["world"] == 2 and result["roundtrip_ok"], result
    # both processes wrote region files + manifests
    files = {p.name for p in (ckpt / "t0").iterdir()}
    assert "zero_offload_proc_0_optim_states.npz" in files, files
    assert "zero_offload_proc_1_optim_states.npz" in files, files
    assert "offload_manifest_0.json" in files and "offload_manifest_1.json" in files
