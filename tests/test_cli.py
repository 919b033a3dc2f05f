"""CLI + launcher tests (reference analogue: tests/test_cli.py, 643 LoC —
config YAML round-trips through launch arg synthesis; and the tier-2
subprocess-launch pattern from SURVEY §4)."""

import json
import os
import subprocess
import sys

import pytest

CPU_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
}


def run_cli(*args, env=None, timeout=240):
    return subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.cli", *args],
        capture_output=True,
        text=True,
        env=env or CPU_ENV,
        timeout=timeout,
    )


def test_env_command():
    result = run_cli("env")
    assert result.returncode == 0
    assert "accelerate_tpu version" in result.stdout
    assert "JAX backend" in result.stdout


def test_estimate_memory_param_count():
    result = run_cli("estimate-memory", "124M", "--num_devices", "4")
    assert result.returncode == 0
    assert "124,000,000" in result.stdout
    assert "bfloat16" in result.stdout


def _fake_hf_cache(tmp_path, repo="acme/tiny", n_rows=10, n_cols=20, index_only=False):
    """A minimal HF hub cache: models--org--name/snapshots/<rev>/ with either
    a real tiny safetensors file or just the index+config metadata."""
    import struct

    hf_home = tmp_path / "hf_home"
    repo_dir = hf_home / "hub" / ("models--" + repo.replace("/", "--"))
    snap = repo_dir / "snapshots" / "rev0"
    snap.mkdir(parents=True)
    (repo_dir / "refs").mkdir()
    (repo_dir / "refs" / "main").write_text("rev0")
    if index_only:
        (snap / "model.safetensors.index.json").write_text(
            json.dumps({"metadata": {"total_size": n_rows * n_cols * 2}, "weight_map": {}})
        )
        (snap / "config.json").write_text(json.dumps({"torch_dtype": "bfloat16"}))
    else:
        header = {"w": {"dtype": "F32", "shape": [n_rows, n_cols], "data_offsets": [0, n_rows * n_cols * 4]}}
        hb = json.dumps(header).encode()
        with open(snap / "model.safetensors", "wb") as f:
            f.write(struct.pack("<Q", len(hb)))
            f.write(hb)
            f.write(b"\0" * (n_rows * n_cols * 4))
    return hf_home


def test_estimate_memory_hub_repo_from_cache(tmp_path):
    """Repo-id source resolves offline from the local HF cache — no network,
    no torch (reference: estimate.py:34-116 needs the full meta-model)."""
    hf_home = _fake_hf_cache(tmp_path, n_rows=30, n_cols=10)
    result = run_cli(
        "estimate-memory", "acme/tiny",
        env={**CPU_ENV, "HF_HOME": str(hf_home), "HF_HUB_OFFLINE": "1"},
    )
    assert result.returncode == 0, result.stderr
    assert "300" in result.stdout and "local cache" in result.stdout


def test_estimate_memory_hub_repo_index_only_cache(tmp_path):
    """With only index.json + config.json cached (no weights), total_size /
    dtype width gives the parameter count."""
    hf_home = _fake_hf_cache(tmp_path, n_rows=40, n_cols=10, index_only=True)
    result = run_cli(
        "estimate-memory", "acme/tiny",
        env={**CPU_ENV, "HF_HOME": str(hf_home), "HF_HUB_OFFLINE": "1"},
    )
    assert result.returncode == 0, result.stderr
    assert "400" in result.stdout and "index total_size" in result.stdout


def test_estimate_memory_hub_repo_unreachable(tmp_path):
    """No cache + no network -> one actionable error naming the offline
    alternatives, not a bare traceback."""
    result = run_cli(
        "estimate-memory", "acme/absent",
        env={**CPU_ENV, "HF_HOME": str(tmp_path / "empty"), "HF_HUB_OFFLINE": "1"},
    )
    assert result.returncode != 0
    assert "could not resolve" in result.stderr and "parameter count like `7B`" in result.stderr


def test_estimate_memory_hub_metadata_mocked(monkeypatch, tmp_path):
    """The network path sums get_safetensors_metadata parameter counts
    (metadata-only ranged requests; no weight download)."""
    import types

    from accelerate_tpu.commands import estimate

    monkeypatch.setenv("HF_HOME", str(tmp_path / "empty"))
    import huggingface_hub

    monkeypatch.setattr(
        huggingface_hub,
        "get_safetensors_metadata",
        lambda repo_id, token=None: types.SimpleNamespace(parameter_count={"BF16": 1000, "F32": 24}),
    )
    n, how = estimate.count_params_from_hub("acme/remote")
    assert n == 1024 and how == "hub safetensors metadata"


def test_estimate_memory_fit_column():
    """--hbm_gb drives a fits/device verdict (north-star sizing aid)."""
    result = run_cli("estimate-memory", "7B", "--num_devices", "8", "--hbm_gb", "16")
    assert result.returncode == 0
    assert "fits/device" in result.stdout
    single = run_cli("estimate-memory", "7B", "--hbm_gb", "16")
    fp32 = [line for line in single.stdout.splitlines() if line.strip().startswith("float32")]
    assert fp32 and fp32[0].rstrip().endswith("no")  # 104 GB Adam state on one 16 GB chip
    sharded = [line for line in result.stdout.splitlines() if line.strip().startswith("float32")]
    assert sharded and sharded[0].rstrip().endswith("yes")  # /8 brings it under HBM


def test_config_roundtrip(tmp_path):
    cfg_path = tmp_path / "cfg.yaml"
    result = run_cli("config", "--default", "--config_file", str(cfg_path))
    assert result.returncode == 0
    from accelerate_tpu.commands.config import load_config

    config = load_config(str(cfg_path))
    assert config["mixed_precision"] == "bf16"
    assert config["mesh_data"] == -1


def test_launch_env_protocol(tmp_path):
    """Launcher flags surface as ACCELERATE_* env in the child
    (reference env protocol: utils/launch.py:203)."""
    script = tmp_path / "dump_env.py"
    script.write_text(
        "import os, json\n"
        "print(json.dumps({k: v for k, v in os.environ.items() if k.startswith('ACCELERATE_')}))\n"
    )
    result = run_cli(
        "launch",
        "--mixed_precision", "bf16",
        "--mesh_fsdp", "2",
        "--gradient_accumulation_steps", "4",
        "--debug",
        str(script),
    )
    assert result.returncode == 0, result.stderr
    env = json.loads(result.stdout.strip().splitlines()[-1])
    assert env["ACCELERATE_MIXED_PRECISION"] == "bf16"
    assert env["ACCELERATE_MESH_FSDP"] == "2"
    assert env["ACCELERATE_GRADIENT_ACCUMULATION_STEPS"] == "4"
    assert env["ACCELERATE_DEBUG_MODE"] == "1"


def test_accelerator_reads_launcher_env(tmp_path):
    """End-to-end: launch flags -> env -> AcceleratorState picks them up."""
    script = tmp_path / "report.py"
    script.write_text(
        "from accelerate_tpu import Accelerator\n"
        "acc = Accelerator()\n"
        "print('MESH', dict(acc.mesh.shape)['fsdp'], acc.mixed_precision, acc.gradient_accumulation_steps)\n"
    )
    result = run_cli(
        "launch", "--cpu", "--fake_devices", "8",
        "--mixed_precision", "bf16", "--mesh_fsdp", "4", "--gradient_accumulation_steps", "2",
        str(script),
    )
    assert result.returncode == 0, result.stderr
    assert "MESH 4 bf16 2" in result.stdout


@pytest.mark.slow
def test_multiprocess_launch(tmp_path):
    """Two real processes with a JAX coordinator (the reference's
    multi-process tier-2 pattern, tests/test_multigpu.py:49)."""
    script = tmp_path / "mp.py"
    script.write_text(
        "from accelerate_tpu import Accelerator\n"
        "acc = Accelerator()\n"
        "assert acc.num_processes == 2, acc.num_processes\n"
        "objs = acc.gather_for_metrics([acc.process_index], use_gather_object=True)\n"
        "assert sorted(objs) == [0, 1], objs\n"
        "acc.wait_for_everyone()\n"
        "print('MP_OK', acc.process_index)\n"
    )
    result = run_cli(
        "launch", "--num_processes", "2", "--cpu", "--fake_devices", "4",
        "--main_process_port", "7811", str(script),
    )
    assert result.returncode == 0, result.stderr + result.stdout
    assert result.stdout.count("MP_OK") >= 1


def test_sync_script_single_process():
    """The self-checking sync-semantics script (reference analogue:
    test_utils/scripts/test_sync.py) through the launcher."""
    result = run_cli(
        "launch", "--cpu", "--fake_devices", "8", "-m",
        "accelerate_tpu.test_utils.scripts.test_sync",
    )
    assert result.returncode == 0, result.stderr + result.stdout
    assert "test_sync: ALL OK" in result.stdout


@pytest.mark.slow
def test_ops_script_multiprocess():
    """Collective-ops script on two real processes (reference analogue:
    test_utils/scripts/test_ops.py)."""
    result = run_cli(
        "launch", "--num_processes", "2", "--cpu", "--fake_devices", "4",
        "--main_process_port", "7813", "-m",
        "accelerate_tpu.test_utils.scripts.test_ops",
    )
    assert result.returncode == 0, result.stderr + result.stdout
    assert result.stdout.count("test_ops: ALL OK") >= 1


@pytest.mark.slow
def test_dcn_script_multiprocess(tmp_path):
    """The DCN legs — orbax multi-host checkpoint save/load (+ reshard-on-
    load), DataLoaderDispatcher scatter, ring attention across processes —
    on a REAL 2-process mesh (reference tier-2 pattern,
    tests/test_multigpu.py:49-53)."""
    result = run_cli(
        "launch", "--num_processes", "2", "--cpu", "--fake_devices", "4",
        "--main_process_port", "7814", "-m",
        "accelerate_tpu.test_utils.scripts.test_dcn", "--tmpdir", str(tmp_path),
        timeout=420,
    )
    assert result.returncode == 0, result.stderr + result.stdout
    for leg in (
        "dispatcher scatter OK",
        "checkpoint save/load across hosts OK",
        "checkpoint reshard-on-load (replicated -> fsdp) OK",
        "ring attention across processes OK",
        "test_dcn: ALL OK",
    ):
        assert leg in result.stdout, f"missing {leg!r}:\n{result.stdout}"


def test_migrate_command(tmp_path):
    """Reference accelerate YAML -> our schema (reference analogue:
    commands/to_fsdp2.py converter)."""
    ref = tmp_path / "ref.yaml"
    ref.write_text(
        "compute_environment: LOCAL_MACHINE\n"
        "distributed_type: FSDP\n"
        "mixed_precision: bf16\n"
        "num_processes: 8\n"
        "num_machines: 2\n"
        "fsdp_config:\n"
        "  fsdp_sharding_strategy: FULL_SHARD\n"
        "  fsdp_activation_checkpointing: true\n"
    )
    out = tmp_path / "ours.yaml"
    result = run_cli("migrate", str(ref), "--output_file", str(out))
    assert result.returncode == 0, result.stderr
    text = out.read_text()
    assert "mesh_fsdp: -1" in text
    assert "mixed_precision: bf16" in text
    assert "num_processes: 8" in text
    # refuses to clobber without --overwrite
    result = run_cli("migrate", str(ref), "--output_file", str(out))
    assert result.returncode != 0
    result = run_cli("migrate", str(ref), "--output_file", str(out), "--overwrite")
    assert result.returncode == 0

    # megatron tp/pp/sp mapping
    ref2 = tmp_path / "ref2.yaml"
    ref2.write_text(
        "distributed_type: MEGATRON_LM\n"
        "num_processes: 16\n"
        "megatron_lm_config:\n"
        "  tp_degree: 4\n"
        "  pp_degree: 2\n"
        "  sequence_parallelism: true\n"
    )
    result = run_cli("migrate", str(ref2))
    assert result.returncode == 0
    assert "mesh_tensor: 4" in result.stdout
    assert "mesh_pipe: 2" in result.stdout
    assert "mesh_seq" in result.stdout


def test_pod_autodiscovery_ssh_fanout(monkeypatch, tmp_path):
    """Bare `launch script.py` on a pod: TPU_WORKER_HOSTNAMES drives the SSH
    fan-out with correct coordinator/process-id wiring (reference:
    tpu_pod_launcher, commands/launch.py:909-965)."""
    from accelerate_tpu.commands import launch as L

    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "tpu-w0,tpu-w1,tpu-w2")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    calls = []

    class FakeProc:
        def __init__(self, cmd, **kw):
            calls.append(cmd)

        def wait(self):
            return 0

    monkeypatch.setattr(L.subprocess, "Popen", FakeProc)
    parser = L.launch_parser()
    args = parser.parse_args(["train.py"])
    rc = L.launch_command(args)
    assert rc == 0
    assert len(calls) == 3
    for rank, cmd in enumerate(calls):
        assert cmd[0] == "ssh"
        remote = cmd[-1]
        assert "ACCELERATE_COORDINATOR_ADDRESS=tpu-w0:7777" in remote
        assert "ACCELERATE_NUM_PROCESSES=3" in remote
        assert f"ACCELERATE_PROCESS_ID={rank}" in remote
        assert f"tpu-w{rank}" in cmd[-2]

    # a non-zero worker defers to worker 0's fan-out
    calls.clear()
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    rc = L.launch_command(parser.parse_args(["train.py"]))
    assert rc == 0 and calls == []


def test_pod_autodiscovery_respects_yaml_topology(monkeypatch, tmp_path):
    """A topology configured in the YAML config file (not just CLI flags)
    must suppress the pod SSH fan-out — the config is a user topology
    request too."""
    from accelerate_tpu.commands import launch as L

    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "tpu-w0,tpu-w1")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    ssh_calls = []
    monkeypatch.setattr(
        L, "pod_ssh_launcher", lambda args: ssh_calls.append(args) or 0
    )
    local_calls = []
    monkeypatch.setattr(
        L, "multi_process_launcher", lambda args: local_calls.append(args) or 0
    )
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text("num_processes: 2\n")
    parser = L.launch_parser()
    rc = L.launch_command(parser.parse_args(["--config_file", str(cfg), "train.py"]))
    assert rc == 0
    assert ssh_calls == [] and len(local_calls) == 1

    # but DEFAULT-valued YAML topology keys (the config wizard writes
    # num_machines: 1 unconditionally) must NOT suppress pod discovery
    ssh_calls.clear()
    local_calls.clear()
    cfg2 = tmp_path / "cfg2.yaml"
    cfg2.write_text("num_machines: 1\nmixed_precision: bf16\n")
    rc = L.launch_command(parser.parse_args(["--config_file", str(cfg2), "train.py"]))
    assert rc == 0
    assert len(ssh_calls) == 1 and local_calls == []


def test_config_precedence_cli_wins(monkeypatch, tmp_path):
    """Explicit CLI flags beat YAML even when they equal a parser default
    (the round-1 sentinel bug: --num_processes 1 was overridden)."""
    from accelerate_tpu.commands import launch as L

    cfg = tmp_path / "cfg.yaml"
    cfg.write_text("num_processes: 8\nmachine_rank: 3\nmixed_precision: bf16\n")
    parser = L.launch_parser()

    args = parser.parse_args(["--config_file", str(cfg), "train.py"])
    L._load_config_into_args(args)
    # not given on the CLI -> YAML fills them
    assert args.num_processes == 8 and args.machine_rank == 3 and args.mixed_precision == "bf16"
    assert "num_processes" in args._from_config

    args = parser.parse_args(
        ["--config_file", str(cfg), "--num_processes", "1", "--machine_rank", "0", "train.py"]
    )
    L._load_config_into_args(args)
    # explicitly passed, equal to defaults -> must NOT be overridden
    assert args.num_processes == 1 and args.machine_rank == 0
    assert args.mixed_precision == "bf16"  # still filled from YAML


def test_launch_starts_one_process_on_an_accelerator(monkeypatch, tmp_path, capsys):
    """The chips of one host belong to one process: off the CPU fake mesh,
    --num_processes N runs ONE process (the notebook_launcher rule), and a
    multi-machine topology that asks for several per host is refused."""
    from accelerate_tpu.commands import launch as L

    calls = []
    monkeypatch.setattr(L, "simple_launcher", lambda a: calls.append(("one", a.num_processes)) or 0)
    monkeypatch.setattr(L, "multi_process_launcher", lambda a: calls.append(("many", a.num_processes)) or 0)
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text("mixed_precision: bf16\n")
    base = ["--config_file", str(cfg), "--no_pod_discovery"]
    parser = L.launch_parser()

    monkeypatch.delenv("JAX_PLATFORMS")
    assert L.launch_command(parser.parse_args([*base, "--num_processes", "4", "train.py"])) == 0
    assert "one process drives every local chip" in capsys.readouterr().err
    with pytest.raises(ValueError, match="one process per host"):
        L.launch_command(parser.parse_args([*base, "--num_processes", "4", "--num_machines", "2", "train.py"]))
    assert L.launch_command(parser.parse_args([*base, "--num_processes", "2", "--num_machines", "2", "train.py"])) == 0
    # the CPU fake mesh keeps spawning, asked for by flag or by environment
    assert L.launch_command(parser.parse_args([*base, "--num_processes", "4", "--cpu", "train.py"])) == 0
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert L.launch_command(parser.parse_args([*base, "--num_processes", "4", "train.py"])) == 0
    assert calls == [("one", 1), ("many", 2), ("many", 4), ("many", 4)]


@pytest.mark.slow
def test_max_restarts_supervisor(tmp_path):
    """Crash-once-then-succeed script: --max_restarts relaunches it with
    ACCELERATE_RESTART_COUNT set (torchelastic analogue; checkpoint-based
    recovery is the script's load_state)."""
    script = tmp_path / "flaky.py"
    script.write_text(
        "import os, pathlib, sys\n"
        f"marker = pathlib.Path({str(tmp_path)!r}) / 'ran_once'\n"
        "if not marker.exists():\n"
        "    marker.write_text('1')\n"
        "    sys.exit(3)\n"
        "assert os.environ['ACCELERATE_RESTART_COUNT'] == '1'\n"
        "print('RECOVERED')\n"
    )
    result = run_cli(
        "launch", "--cpu", "--max_restarts", "1", "--monitor_interval", "0.1", str(script)
    )
    assert result.returncode == 0, result.stderr + result.stdout
    assert "RECOVERED" in result.stdout

    # without supervision the crash propagates
    (tmp_path / "ran_once").unlink()
    result = run_cli("launch", "--cpu", str(script))
    assert result.returncode == 3


@pytest.mark.slow
def test_max_restarts_multiprocess_group_restart(tmp_path):
    """One rank crashing takes the group down; the supervisor relaunches
    the whole group and the retry succeeds."""
    script = tmp_path / "flaky_mp.py"
    script.write_text(
        "import os, pathlib, sys\n"
        f"base = pathlib.Path({str(tmp_path)!r})\n"
        "rank = os.environ.get('ACCELERATE_PROCESS_ID', '0')\n"
        "attempt = os.environ['ACCELERATE_RESTART_COUNT']\n"
        "(base / f'saw_{rank}_{attempt}').write_text('1')\n"
        "if attempt == '0' and rank == '1':\n"
        "    sys.exit(5)\n"
        "print('MP_RECOVERED', rank)\n"
    )
    result = run_cli(
        "launch", "--num_processes", "2", "--cpu", "--fake_devices", "4",
        "--main_process_port", "7917", "--max_restarts", "1",
        "--monitor_interval", "0.1", str(script),
        timeout=300,
    )
    assert result.returncode == 0, result.stderr + result.stdout
    # both attempts ran both ranks
    for rank in (0, 1):
        for attempt in (0, 1):
            assert (tmp_path / f"saw_{rank}_{attempt}").exists(), (rank, attempt)
    assert result.stdout.count("MP_RECOVERED") >= 1


@pytest.mark.slow
def test_compression_script_multiprocess():
    """Compressed gradient reduction across two REAL processes (the
    multi-host DCN case the comm-hook analogue exists for)."""
    result = run_cli(
        "launch", "--num_processes", "2", "--cpu", "--fake_devices", "4", "-m",
        "accelerate_tpu.test_utils.scripts.test_compression",
        timeout=420,
    )
    assert result.returncode == 0, result.stderr + result.stdout
    assert result.stdout.count("test_compression: ALL OK") >= 1


@pytest.mark.slow
def test_data_loop_script_multiprocess():
    """Distributed data-loop script (reference analogue:
    test_utils/scripts/test_distributed_data_loop.py) on two processes."""
    result = run_cli(
        "launch", "--num_processes", "2", "--cpu", "--fake_devices", "4",
        "--main_process_port", "7815", "-m",
        "accelerate_tpu.test_utils.scripts.test_data_loop",
        timeout=300,
    )
    assert result.returncode == 0, result.stderr + result.stdout
    assert result.stdout.count("test_data_loop: ALL OK") >= 1


def test_config_update_migrates_legacy_keys(tmp_path):
    """`config --update` renames legacy keys and drops unknown ones
    (reference analogue: accelerate config update)."""
    cfg = tmp_path / "old.yaml"
    cfg.write_text("dp: 4\nprecision: bf16\nmystery_key: 1\nnum_processes: 2\n")
    result = run_cli("config", "--update", "--config_file", str(cfg))
    assert result.returncode == 0, result.stderr
    from accelerate_tpu.commands.config import load_config

    migrated = load_config(str(cfg))
    assert migrated == {"mesh_data": 4, "mixed_precision": "bf16", "num_processes": 2}
    assert "mystery_key" in result.stdout

    # missing file is a clean error
    result = run_cli("config", "--update", "--config_file", str(tmp_path / "nope.yaml"))
    assert result.returncode == 1


def test_config_update_protects_current_keys_and_bad_casts(tmp_path):
    cfg = tmp_path / "half.yaml"
    cfg.write_text("mixed_precision: bf16\nprecision: fp16\n")
    result = run_cli("config", "--update", "--config_file", str(cfg))
    assert result.returncode == 0, result.stderr
    from accelerate_tpu.commands.config import load_config

    # the stale legacy spelling must not clobber the current value
    assert load_config(str(cfg))["mixed_precision"] == "bf16"

    bad = tmp_path / "bad.yaml"
    bad.write_text("dp: auto\n")
    result = run_cli("config", "--update", "--config_file", str(bad))
    assert result.returncode == 1
    assert "cannot migrate" in result.stdout and "Traceback" not in result.stderr


def test_config_update_reports_dropped_legacy_regardless_of_order(tmp_path):
    """When both the legacy and current spelling are present, the current
    value wins AND the legacy key is reported dropped in either file
    order."""
    from accelerate_tpu.commands.config import load_config

    for text in ("precision: fp16\nmixed_precision: bf16\n", "mixed_precision: bf16\nprecision: fp16\n"):
        cfg = tmp_path / "cfg.yaml"
        cfg.write_text(text)
        result = run_cli("config", "--update", "--config_file", str(cfg))
        assert result.returncode == 0, result.stderr
        assert load_config(str(cfg))["mixed_precision"] == "bf16"
        assert "precision" in result.stdout and "dropped" in result.stdout, (text, result.stdout)


@pytest.mark.slow
def test_performance_gate_script():
    """Accuracy-floor regression gates per mesh layout (reference analogue:
    external_deps/test_performance.py MRPC thresholds per strategy)."""
    result = run_cli(
        "launch", "--cpu", "--fake_devices", "8", "-m",
        "accelerate_tpu.test_utils.scripts.test_performance",
        timeout=900,
    )
    assert result.returncode == 0, result.stderr + result.stdout
    assert "test_performance: ALL OK" in result.stdout


@pytest.mark.slow
def test_manual_multi_machine_launch(tmp_path):
    """Manual multi-machine topology (reference: multi_gpu_launcher node
    ranks, commands/launch.py:790-822): the launcher is invoked ONCE PER
    MACHINE with --machine_rank 0/1 against one coordinator; global ranks
    are machine_rank * procs_per_machine + local_rank and the 2x2 group
    trains as four processes."""
    script = tmp_path / "mm.py"
    script.write_text(
        "import numpy as np\n"
        "import optax\n"
        "from accelerate_tpu import Accelerator\n"
        "from accelerate_tpu.test_utils import RegressionDataset, RegressionModel, linear_loss_fn\n"
        "acc = Accelerator()\n"
        "assert acc.num_processes == 4, acc.num_processes\n"
        "ranks = acc.gather_for_metrics([acc.process_index], use_gather_object=True)\n"
        "assert sorted(ranks) == [0, 1, 2, 3], ranks\n"
        "model = acc.prepare_model(RegressionModel())\n"
        "acc.prepare_optimizer(optax.sgd(0.1))\n"
        "step = acc.build_train_step(linear_loss_fn)\n"
        "ds = RegressionDataset(length=64, seed=0)\n"
        "losses = [float(step({'x': ds.x[:16], 'y': ds.y[:16]})) for _ in range(20)]\n"
        "assert losses[-1] < losses[0], losses\n"
        "print('MULTI_MACHINE_OK', acc.process_index)\n"
    )
    common = [
        sys.executable, "-m", "accelerate_tpu.commands.cli", "launch",
        "--num_processes", "4", "--num_machines", "2",
        "--main_process_ip", "127.0.0.1", "--main_process_port", "7831",
        "--cpu", "--fake_devices", "2",
    ]
    procs = [
        subprocess.Popen(
            [*common, "--machine_rank", str(mr), str(script)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=CPU_ENV,
        )
        for mr in (0, 1)
    ]
    outs = [p.communicate(timeout=420)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), "\n---\n".join(outs)
    assert "MULTI_MACHINE_OK" in "".join(outs)


@pytest.mark.slow
def test_multi_machine_rejects_indivisible_topology(tmp_path):
    script = tmp_path / "noop.py"
    script.write_text("print('never runs')\n")
    result = run_cli(
        "launch", "--num_processes", "3", "--num_machines", "2", "--cpu",
        str(script),
    )
    assert result.returncode != 0
    assert "divisible" in result.stderr


@pytest.mark.slow
def test_script_multiprocess():
    """The canonical "does distributed work" script (reference analogue:
    test_utils/scripts/test_script.py run by tests/test_multigpu.py:49)
    under two REAL processes."""
    result = run_cli(
        "launch", "--num_processes", "2", "--cpu", "--fake_devices", "4",
        "--main_process_port", "7829", "-m",
        "accelerate_tpu.test_utils.scripts.test_script",
        timeout=600,
    )
    assert result.returncode == 0, result.stderr + result.stdout
    assert result.stdout.count("ALL CHECKS PASSED") >= 1


@pytest.mark.slow
def test_checkpoint_resume_script_multiprocess(tmp_path):
    """2-process orbax checkpoint round-trip through the real launcher
    (reference analogue: test_state_checkpointing.py, run distributed)."""
    env = {**CPU_ENV, "ACCELERATE_TEST_CKPT_DIR": str(tmp_path / "ck")}
    result = run_cli(
        "launch", "--num_processes", "2", "--cpu", "--fake_devices", "4",
        "--main_process_port", "7823", "-m",
        "accelerate_tpu.test_utils.scripts.test_checkpoint_resume",
        env=env, timeout=420,
    )
    assert result.returncode == 0, result.stderr + result.stdout
    assert "test_checkpoint_resume: ALL OK" in result.stdout


def test_config_yaml_templates_are_valid():
    """Every shipped template (examples/config_yaml_templates/, reference
    analogue: the same directory upstream) round-trips through the real
    loader with no key silently dropped."""
    import pathlib

    from accelerate_tpu.commands.config import CONFIG_KEYS, load_config, _load_yaml

    tdir = pathlib.Path(__file__).parent.parent / "examples" / "config_yaml_templates"
    templates = sorted(tdir.glob("*.yaml"))
    assert len(templates) >= 6, templates
    for path in templates:
        raw = _load_yaml(path.read_text())
        unknown = set(raw) - set(CONFIG_KEYS)
        assert not unknown, f"{path.name}: unknown keys {unknown}"
        loaded = load_config(str(path))
        assert set(loaded) == set(raw), f"{path.name}: keys dropped by loader"
        assert loaded["num_processes"] >= 1 and loaded["num_machines"] >= 1


@pytest.mark.slow
def test_config_template_run_me():
    """run_me.py launches under a template with CLI overrides winning
    (reference: config_yaml_templates/run_me.py)."""
    import pathlib

    tdir = pathlib.Path(__file__).parent.parent / "examples" / "config_yaml_templates"
    result = run_cli(
        "launch", "--config_file", str(tdir / "hybrid_mesh.yaml"),
        "--num_processes", "1", "--cpu", "--fake_devices", "8",
        str(tdir / "run_me.py"), timeout=300,
    )
    assert result.returncode == 0, result.stderr + result.stdout
    assert "Accelerator state" in result.stdout


# --------------------------------------------------------------------- #
# accelerate-tpu lint (the TPU correctness linter CLI)
# --------------------------------------------------------------------- #


def test_lint_repo_tree_clean():
    """The package tree must carry zero error-severity findings."""
    import pathlib

    pkg = pathlib.Path(__file__).parent.parent / "accelerate_tpu"
    result = run_cli("lint", str(pkg))
    assert result.returncode == 0, result.stdout + result.stderr
    assert "0 error(s)" in result.stdout


def test_lint_detects_seeded_defects_and_exits_nonzero(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        '"""Fixture."""\n'
        "import jax\n"
        "\n"
        "\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    if x > 0:\n"
        "        return jax.device_get(x)\n"
        "    return x\n"
    )
    result = run_cli("lint", str(bad))
    assert result.returncode == 1, result.stdout + result.stderr
    assert "TPU201" in result.stdout  # device_get in jit (error)
    assert "TPU202" in result.stdout  # tracer branch (warning)
    assert f"{bad}:8: TPU201" in result.stdout  # path:line: TPUxxx format


def test_lint_json_format(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import os\n")
    result = run_cli("lint", str(bad), "--format", "json")
    payload = json.loads(result.stdout)
    assert {f["rule"] for f in payload} == {"TPU001", "TPU002"}
    assert all(f["severity"] == "error" for f in payload)


def test_lint_select_ignore_and_suppression(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import os  # tpu-lint: disable=TPU001\n")
    result = run_cli("lint", str(bad), "--ignore", "TPU002")
    assert result.returncode == 0, result.stdout
    assert "0 finding(s)" in result.stdout


def test_lint_sarif_format(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import os\n")
    result = run_cli("lint", str(bad), "--format", "sarif")
    doc = json.loads(result.stdout)
    assert doc["version"] == "2.1.0"
    results = doc["runs"][0]["results"]
    assert {r["ruleId"] for r in results} == {"TPU001", "TPU002"}
    uri = results[0]["locations"][0]["physicalLocation"]["artifactLocation"]["uri"]
    assert uri == str(bad)


@pytest.mark.slow
def test_lint_selfcheck():
    """Every rule detects its seeded-defect fixture (CPU fake mesh)."""
    result = run_cli("lint", "--selfcheck")
    assert result.returncode == 0, result.stdout + result.stderr
    assert result.stdout.count("detected") == 44  # 6 AST + 4 jaxpr + 3 flight + 5 divergence + 5 perf + 6 numerics + 5 config + 5 pipe + 5 fleet
    assert "honoured" in result.stdout
    assert "clean idiomatic script: zero findings" in result.stdout


# --------------------------------------------------------------------------- #
# accelerate-tpu fleet-check (TPU9xx host-concurrency + protocol gate)
# --------------------------------------------------------------------------- #

_DEADLOCK_SRC = """\
import threading

class Router:
    def __init__(self):
        self._lock = threading.Lock()
        self._stats_lock = threading.Lock()

    def route(self):
        with self._lock:
            with self._stats_lock:
                pass

    def report(self):
        with self._stats_lock:
            with self._lock:
                pass
"""


def test_fleet_check_dogfoods_clean_and_proves_protocol():
    result = run_cli(
        "fleet-check",
        "accelerate_tpu/serving_fleet.py", "accelerate_tpu/scheduling.py", "accelerate_tpu/ft",
    )
    assert result.returncode == 0, result.stdout + result.stderr
    assert "protocol:" in result.stdout and "states explored" in result.stdout
    assert "0 finding(s)" in result.stdout


def test_fleet_check_selfcheck():
    result = run_cli("fleet-check", "--selfcheck")
    assert result.returncode == 0, result.stdout + result.stderr
    assert result.stdout.count("detected") == 5  # TPU901/902/903/905 + 904
    assert result.stdout.count("clean twin") == 5
    assert "MISSED" not in result.stdout and "DIRTY" not in result.stdout


def test_fleet_check_seeded_deadlock_gates_strictly(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(_DEADLOCK_SRC)
    result = run_cli("fleet-check", str(bad), "--no-protocol")
    assert result.returncode == 1  # TPU901 is error severity: strict by default
    assert "TPU901" in result.stdout

    sarif = run_cli("fleet-check", str(bad), "--no-protocol", "--format", "sarif")
    doc = json.loads(sarif.stdout)
    assert [r["ruleId"] for r in doc["runs"][0]["results"]] == ["TPU901"]


def test_fleet_check_json_embeds_full_coverage_map():
    result = run_cli("fleet-check", "--format", "json")
    assert result.returncode == 0, result.stdout + result.stderr
    doc = json.loads(result.stdout)
    assert doc["findings"] == []
    proto = doc["protocol"]
    assert proto["explored_states"] > 1000 and not proto["truncated"]
    # model-checks = chaos-observes: every explored path pinned to a test
    assert proto["coverage"] and all(t for t in proto["coverage"].values())
    assert proto["coverage"]["poison/quarantine_no_kv"].startswith("test_chaos_poison")


def _seed_git_repo(repo):
    def git(*a):
        subprocess.run(
            ["git", *a], cwd=repo, capture_output=True, check=True,
            env={**CPU_ENV, "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
                 "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t", "HOME": str(repo)},
        )
    git("init", "-b", "main")
    # a committed file with findings that --changed must NOT rescan
    (repo / "old.py").write_text("import os\n")
    git("add", "-A")
    git("commit", "-m", "seed")


def test_lint_changed_scopes_to_git_touched_files(tmp_path):
    repo = tmp_path / "repo"
    repo.mkdir()
    _seed_git_repo(repo)
    (repo / "new.py").write_text("import os\n")  # untracked: in scope
    result = subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.cli", "lint", "--changed", "--format", "json"],
        capture_output=True, text=True, env=CPU_ENV, cwd=repo, timeout=240,
    )
    assert result.returncode == 1, result.stdout + result.stderr  # TPU001 is an error
    paths = {f["path"] for f in json.loads(result.stdout)}
    assert paths and all(p.endswith("new.py") for p in paths), paths


def test_divergence_changed_scopes_too(tmp_path):
    repo = tmp_path / "repo"
    repo.mkdir()
    _seed_git_repo(repo)
    (repo / "diverge.py").write_text(
        '"""Changed file with a rank-divergent gather."""\n'
        "def main(accelerator):\n"
        "    if accelerator.is_main_process:\n"
        "        accelerator.gather(1)\n"
    )
    result = subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.cli", "divergence", "--changed", "--format", "json"],
        capture_output=True, text=True, env=CPU_ENV, cwd=repo, timeout=240,
    )
    assert result.returncode == 1, result.stdout + result.stderr
    findings = json.loads(result.stdout)
    assert {f["rule"] for f in findings} == {"TPU401"}
    assert all(f["path"].endswith("diverge.py") for f in findings)


def test_fleet_check_changed_scopes_too(tmp_path):
    repo = tmp_path / "repo"
    repo.mkdir()
    _seed_git_repo(repo)
    (repo / "dead.py").write_text(_DEADLOCK_SRC)
    result = subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.cli", "fleet-check",
         "--changed", "--no-protocol"],
        capture_output=True, text=True, env=CPU_ENV, cwd=repo, timeout=240,
    )
    assert result.returncode == 1
    assert "TPU901" in result.stdout and "old.py" not in result.stdout


def test_lint_sarif_merges_six_runs(tmp_path):
    """The Makefile's lint-sarif artifact carries one runs[] entry per
    analysis tier — AST, divergence, numerics, pipe, fleet, kernel. Pin
    the count in the recipe AND prove merge_sarif keeps all six."""
    makefile = open(os.path.join(os.path.dirname(__file__), "..", "Makefile")).read()
    recipe = makefile.split("lint-sarif:")[1].split("\n\n")[0]
    inputs = [tok for tok in recipe.split() if tok.startswith(".cache/") and tok.endswith(".sarif")]
    merge_line = next(l for l in recipe.splitlines() if "merge_sarif.py" in l)
    merged_inputs = [t for t in merge_line.split() if t.endswith(".sarif") and t != "lint-merged.sarif"]
    assert len(merged_inputs) == 6, merged_inputs
    assert ".cache/fleet.sarif" in merged_inputs and ".cache/kernel.sarif" in merged_inputs
    assert sorted(set(inputs)) == sorted(merged_inputs)

    from accelerate_tpu.analysis import Finding, render_sarif

    files = []
    for i in range(6):
        p = tmp_path / f"run{i}.sarif"
        p.write_text(render_sarif([Finding("TPU901", f"finding {i}")]))
        files.append(str(p))
    merged_path = tmp_path / "merged.sarif"
    repo = os.path.join(os.path.dirname(__file__), "..")
    result = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "merge_sarif.py"), *files,
         "-o", str(merged_path)],
        capture_output=True, text=True, env=CPU_ENV,
    )
    assert result.returncode == 0, result.stderr
    assert len(json.loads(merged_path.read_text())["runs"]) == 6


# --------------------------------------------------------------------------- #
# accelerate-tpu checkpoints (fault-tolerance CLI)
# --------------------------------------------------------------------------- #


def _seed_checkpoint_fixtures(base):
    """Seed one good, one corrupt, and one uncommitted checkpoint using
    the manifest layer directly (no jax in the test process)."""
    import pickle

    from accelerate_tpu.ft.manifest import TMP_SUFFIX, build_manifest, write_manifest
    from accelerate_tpu.test_utils.fault_injection import corrupt_file

    def seed(n):
        d = base / f"checkpoint_{n}"
        (d / "model").mkdir(parents=True)
        (d / "model" / "arrays.bin").write_bytes(bytes(range(256)))
        (d / "accelerate_state.json").write_text(json.dumps({"step": n * 10, "save_iteration": n}))
        with open(d / "rng_state_0.pkl", "wb") as f:
            pickle.dump({"seed": 1}, f)
        write_manifest(d, build_manifest(d, step=n * 10, iteration=n))
        return d

    seed(0)
    corrupt_file(seed(1) / "accelerate_state.json", mode="garbage")
    partial = base / f"checkpoint_2{TMP_SUFFIX}"
    partial.mkdir(parents=True)
    (partial / "half_written.bin").write_bytes(b"x" * 32)


def test_checkpoints_list_and_verify(tmp_path):
    base = tmp_path / "checkpoints"
    _seed_checkpoint_fixtures(base)

    result = run_cli("checkpoints", "list", str(base), "--deep", "--format", "json")
    assert result.returncode == 0, result.stderr
    rows = {r["name"]: r for r in json.loads(result.stdout)["checkpoints"]}
    assert rows["checkpoint_0"]["valid"] and rows["checkpoint_0"]["step"] == 0
    assert not rows["checkpoint_1"]["valid"]
    assert "uncommitted" in rows["checkpoint_2.tmp"]["state"]

    result = run_cli("checkpoints", "verify", str(base))
    assert result.returncode == 1  # one checkpoint is corrupt
    assert "[OK ] checkpoint_0" in result.stdout
    assert "[BAD] checkpoint_1" in result.stdout and "crc32" in result.stdout

    result = run_cli("checkpoints", "verify", str(base / "checkpoint_0"))
    assert result.returncode == 0, result.stdout


def test_checkpoints_gc(tmp_path):
    base = tmp_path / "checkpoints"
    _seed_checkpoint_fixtures(base)

    result = run_cli("checkpoints", "gc", str(base), "--dry-run")
    assert result.returncode == 0
    assert (base / "checkpoint_2.tmp").exists(), "dry-run must not delete"

    result = run_cli("checkpoints", "gc", str(base), "--format", "json")
    assert result.returncode == 0
    report = json.loads(result.stdout)
    assert "checkpoint_2.tmp" in report["removed"]
    assert not (base / "checkpoint_2.tmp").exists()


def _seed_topology_checkpoint(base):
    """A committed checkpoint whose (v2) manifest carries a topology
    record — saved on mesh data=4, 2 processes."""
    from accelerate_tpu.ft.manifest import build_manifest, write_manifest

    d = base / "checkpoint_0"
    (d / "model").mkdir(parents=True)
    (d / "model" / "arrays.bin").write_bytes(bytes(range(64)))
    (d / "accelerate_state.json").write_text(json.dumps({"step": 12, "seed": 5}))
    topology = {
        "schema_version": 1,
        "process_count": 2,
        "mesh_shape": {"data": 4, "tensor": 1},
        "mesh_devices": 4,
        "dcn_axes": [],
        "data_parallel_degree": 4,
        "seed": 5,
        "arrays": {
            "model['w']": {"shape": [16, 16], "dtype": "float32", "spec": ["data", None], "bytes": 1024},
        },
    }
    write_manifest(d, build_manifest(d, step=12, iteration=0, topology=topology))
    return d


def test_checkpoints_describe_matching_and_mismatching(tmp_path):
    base = tmp_path / "checkpoints"
    ck = _seed_topology_checkpoint(base)

    # no --mesh: checked against the saved topology itself -> identical
    result = run_cli("checkpoints", "describe", str(ck), "--format", "json")
    assert result.returncode == 0, result.stderr
    info = json.loads(result.stdout)
    assert info["compatibility"] == "identical"
    assert info["reshard"]["total_bytes"] == 0
    assert info["saved_topology"]["mesh_shape"]["data"] == 4

    # mismatching target mesh -> elastic, with a nonzero reshard estimate
    result = run_cli(
        "checkpoints", "describe", str(ck),
        "--mesh", "data=4,fsdp=2", "--dcn-axes", "fsdp", "--processes", "4",
        "--format", "json",
    )
    assert result.returncode == 0, result.stderr
    info = json.loads(result.stdout)
    assert info["compatibility"] == "elastic"
    assert any("process count" in c for c in info["changes"])
    assert info["reshard"]["dcn_bytes"] == 1024 // 2  # 2-way DCN ring stage
    assert info["reshard"]["ici_bytes"] == 1024 * 3 // 4  # 4-way ICI stage

    # text output names the verdict and the traffic split
    result = run_cli("checkpoints", "describe", str(ck), "--mesh", "data=8")
    assert result.returncode == 0
    assert "ELASTIC" in result.stdout and "predicted reshard traffic" in result.stdout
    # base-dir form resolves to the newest valid checkpoint
    result = run_cli("checkpoints", "describe", str(base))
    assert result.returncode == 0
    assert "IDENTICAL" in result.stdout


def test_checkpoints_describe_no_topology(tmp_path):
    base = tmp_path / "checkpoints"
    _seed_checkpoint_fixtures(base)  # v2 manifests without topology blocks
    result = run_cli("checkpoints", "describe", str(base / "checkpoint_0"), "--format", "json")
    assert result.returncode == 0, result.stderr
    info = json.loads(result.stdout)
    assert info["compatibility"] == "unknown"
    assert info["saved_topology"] is None


def test_checkpoints_selfcheck():
    """The make ft-selfcheck gate: seeded fixtures classify correctly."""
    result = run_cli("checkpoints", "verify", "--selfcheck")
    assert result.returncode == 0, result.stdout + result.stderr
    assert "[checkpoints selfcheck] OK" in result.stdout
    assert "describe classifies" in result.stdout
