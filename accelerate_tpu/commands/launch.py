"""``accelerate-tpu launch`` — process spawner.

Reference analogue: src/accelerate/commands/launch.py (1209 LoC): ~120 flags
merged with YAML config, routed to torchrun / deepspeed / xmp.spawn / pod-SSH
launchers. The TPU-native launcher is radically simpler because JAX SPMD
needs **one process per host**, not one per accelerator:

* single host (1 process, N chips): exec the script with the env protocol
  set — no spawning at all;
* multi-process on one machine (CPU fake-mesh testing / explicit
  ``--num_processes``): spawn N processes with a local coordinator, each
  pinned to its devices;
* TPU pod: one process per pod host, discovered from GCE metadata or
  ``--hosts``, launched over SSH re-invoking this launcher per host
  (reference tpu_pod_launcher: commands/launch.py:909-965).

Config channel stays env vars (``ACCELERATE_*`` protocol, reference:
utils/launch.py:203-352).
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys



def _pkg_root() -> str:
    """Directory containing the ``accelerate_tpu`` package (the checkout
    root when not pip-installed)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def launch_parser(subparsers=None):
    if subparsers is not None:
        parser = subparsers.add_parser("launch", help="Launch a training script on this host/pod")
    else:
        parser = argparse.ArgumentParser("accelerate-tpu launch")
    parser.add_argument("--num_processes", type=int, default=1, help="processes to spawn (hosts on a pod)")
    parser.add_argument("--num_machines", type=int, default=1)
    parser.add_argument("--machine_rank", type=int, default=0)
    parser.add_argument("--main_process_ip", default="127.0.0.1")
    parser.add_argument("--main_process_port", type=int, default=None)
    parser.add_argument("--mixed_precision", default=None, choices=["no", "bf16", "fp16", "fp8"])
    parser.add_argument("--gradient_accumulation_steps", type=int, default=None)
    parser.add_argument("--mesh_data", type=int, default=None)
    parser.add_argument("--mesh_fsdp", type=int, default=None)
    parser.add_argument("--mesh_tensor", type=int, default=None)
    parser.add_argument("--mesh_seq", type=int, default=None)
    parser.add_argument("--mesh_pipe", type=int, default=None)
    parser.add_argument("--mesh_expert", type=int, default=None)
    parser.add_argument("--debug", action="store_true", help="enable collective shape verification")
    parser.add_argument(
        "--max_restarts",
        type=int,
        default=0,
        help="restart the run this many times on crash (checkpoint-based resume; torchelastic analogue)",
    )
    parser.add_argument(
        "--monitor_interval",
        type=float,
        default=5,
        help="seconds between process-group health polls / before a restart",
    )
    parser.add_argument("--cpu", action="store_true", help="force the CPU backend")
    parser.add_argument("--fake_devices", type=int, default=None, help="CPU fake-mesh device count (testing)")
    parser.add_argument("--config_file", default=None)
    parser.add_argument("--tpu_hosts", default=None, help="comma-separated pod host list for SSH fan-out")
    parser.add_argument("--ssh_user", default=None)
    parser.add_argument(
        "-m",
        "--module",
        action="store_true",
        help="interpret training_script as a python module path (python -m), reference: launch.py --module",
    )
    parser.add_argument(
        "--no_pod_discovery",
        action="store_true",
        help="disable GCE TPU pod autodiscovery (forces a local launch on pod VMs)",
    )
    parser.add_argument("training_script", help="script (or module with -m) to launch")
    parser.add_argument("training_script_args", nargs=argparse.REMAINDER, default=[])
    if subparsers is not None:
        parser.set_defaults(func=launch_command)
    return _track_explicit(parser)


def _track_explicit(parser):
    """Record which option dests were explicitly provided on the CLI, in
    ``namespace._explicit``. argparse invokes an option's Action only when
    the flag is actually present, so this is exact — unlike scanning
    ``sys.argv`` it ignores the training script's own args and handles
    ``--flag=value`` and prefix abbreviations."""

    def tracked(cls):
        class Tracked(cls):
            def __call__(self, p, ns, values, option_string=None):
                if getattr(ns, "_explicit", None) is None:
                    ns._explicit = set()
                ns._explicit.add(self.dest)
                super().__call__(p, ns, values, option_string)

        return Tracked

    for action in parser._actions:
        if action.option_strings and not isinstance(action, argparse._HelpAction):
            action.__class__ = tracked(type(action))
    return parser


def build_env(args, process_id: int = 0, num_processes: int = 1) -> dict:
    """The launcher->script env protocol (reference: utils/launch.py:203)."""
    env = os.environ.copy()
    # The framework may be run straight from a checkout (not pip-installed);
    # the child script's sys.path[0] is its own directory, so make sure the
    # package stays importable in the child.
    env["PYTHONPATH"] = _pkg_root() + os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else _pkg_root()
    if args.mixed_precision:
        env["ACCELERATE_MIXED_PRECISION"] = args.mixed_precision
    if args.gradient_accumulation_steps:
        env["ACCELERATE_GRADIENT_ACCUMULATION_STEPS"] = str(args.gradient_accumulation_steps)
    for axis in ("data", "fsdp", "tensor", "seq", "pipe", "expert"):
        val = getattr(args, f"mesh_{axis}")
        if val is not None:
            env[f"ACCELERATE_MESH_{axis.upper()}"] = str(val)
    if args.debug:
        env["ACCELERATE_DEBUG_MODE"] = "1"
    if num_processes > 1:
        port = args.main_process_port or 7777
        env["ACCELERATE_COORDINATOR_ADDRESS"] = f"{args.main_process_ip}:{port}"
        env["ACCELERATE_NUM_PROCESSES"] = str(num_processes)
        env["ACCELERATE_PROCESS_ID"] = str(process_id)
        # local rank within this machine: the N-local-process testing
        # launcher would otherwise make every process "local main"
        # (state.local_process_index defaults to 0 for 1-proc-per-host pods)
        procs_per_machine = num_processes // max(1, getattr(args, "num_machines", 1) or 1)
        env["ACCELERATE_LOCAL_PROCESS_ID"] = str(process_id % max(1, procs_per_machine))
    if args.cpu or args.fake_devices:
        env["JAX_PLATFORMS"] = "cpu"
        if args.fake_devices:
            env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={args.fake_devices}"
    return env


def _load_config_into_args(args):
    """Config-precedence contract: CLI > YAML > parser defaults
    (reference: _validate_launch_command, commands/launch.py:988).
    Explicitly-passed flags are tracked by the parser itself
    (``args._explicit`` — see :func:`_track_explicit`)."""
    if args.config_file is None:
        from .config import default_config_path

        if os.path.isfile(default_config_path()):
            args.config_file = default_config_path()
        else:
            return args
    from .config import load_config

    explicit = getattr(args, "_explicit", None) or set()
    config = load_config(args.config_file)
    applied = set()
    for key, value in config.items():
        if hasattr(args, key) and key not in explicit:
            setattr(args, key, value)
            applied.add(key)
    # a topology configured in the YAML counts as a user topology request
    # (launch_command must not hijack it into pod SSH fan-out)
    args._from_config = applied
    return args


def discover_pod_hosts() -> list | None:
    """GCE TPU pod worker autodiscovery (reference: tpu_pod_launcher,
    commands/launch.py:909-965 + SURVEY §2.5 "launch reads TPU pod
    metadata"). Sources, in order: the ``TPU_WORKER_HOSTNAMES`` env the TPU
    runtime sets on every pod VM, then the GCE metadata server. Returns the
    host list when this machine is part of a multi-host pod, else None."""
    names = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if not names:
        try:  # metadata server: only reachable on GCE VMs; fail fast
            import urllib.request

            req = urllib.request.Request(
                "http://metadata.google.internal/computeMetadata/v1/instance/attributes/worker-network-endpoints",
                headers={"Metadata-Flavor": "Google"},
            )
            with urllib.request.urlopen(req, timeout=2) as resp:
                # format: "ip:port:...,ip:port:..." — keep the ip part
                endpoints = resp.read().decode()
            names = ",".join(e.split(":")[0] for e in endpoints.split(",") if e)
        except Exception:
            return None
    hosts = [h.strip() for h in names.split(",") if h.strip()]
    return hosts if len(hosts) > 1 else None


def pod_worker_id() -> int:
    return int(os.environ.get("TPU_WORKER_ID", "0"))


def _supervised(run_once, args) -> int:
    """Per-host process supervision — restart-on-crash up to
    ``--max_restarts`` times (reference analogue: the torchelastic
    ``max_restarts``/``monitor_interval`` args the reference forwards,
    commands/launch.py elastic group; SURVEY §5 lists this as the
    framework's failure-recovery story). Recovery is checkpoint-based: the
    restarted script sees ``ACCELERATE_RESTART_COUNT`` and its own
    ``load_state`` resumes from the last checkpoint."""
    import time

    max_restarts = getattr(args, "max_restarts", 0) or 0
    attempt = 0
    while True:
        rc = run_once(attempt)
        if rc == 0 or attempt >= max_restarts:
            if rc != 0:
                from ..utils.console import print_launch_failure

                print_launch_failure(rc, attempt if max_restarts else None)
            return rc
        attempt += 1
        delay = getattr(args, "monitor_interval", None)
        delay = 5 if delay is None else delay
        print(
            f"launch: run failed (rc={rc}); restart {attempt}/{max_restarts} in {delay}s",
            file=sys.stderr,
        )
        time.sleep(delay)


def simple_launcher(args) -> int:
    """One process for all local chips (reference simple_launcher:
    commands/launch.py:778)."""

    def run_once(attempt):
        env = build_env(args)
        env["ACCELERATE_RESTART_COUNT"] = str(attempt)
        cmd = [sys.executable, *_script_argv(args)]
        return subprocess.call(cmd, env=env)

    return _supervised(run_once, args)


def _script_argv(args) -> list:
    if getattr(args, "module", False):
        return ["-m", args.training_script, *args.training_script_args]
    return [args.training_script, *args.training_script_args]


def multi_process_launcher(args) -> int:
    """N local processes with a JAX coordinator (testing / multi-host-sim;
    replaces torchrun — reference: commands/launch.py:790-822). A process
    crashing takes the whole group down (the collective would deadlock
    anyway), then ``--max_restarts`` relaunches the group.

    Manual multi-machine topology (GKE jobs, clusters without SSH trust —
    reference: multi_gpu_launcher node-rank offsets, commands/launch.py:790
    + utils/launch.py:203-352): the user runs this launcher once per
    machine with the same ``--num_processes`` (GLOBAL total), the same
    ``--main_process_ip``/``--main_process_port`` (machine 0 = coordinator)
    and that machine's ``--machine_rank``; each machine spawns its local
    share with ``process_id = machine_rank * procs_per_machine +
    local_rank``."""
    import time

    num_machines = getattr(args, "num_machines", 1) or 1
    total = args.num_processes
    if total % num_machines != 0:
        raise ValueError(
            f"--num_processes ({total}) is the GLOBAL process count and must be "
            f"divisible by --num_machines ({num_machines})"
        )
    if num_machines > 1 and (getattr(args, "max_restarts", 0) or 0) > 0:
        # this launcher only supervises ITS machine's share: restarting one
        # machine's ranks while the other machines' ranks still block in
        # collectives (and the coordinator holds the old group) hangs the
        # job instead of recovering it. Coordinated multi-machine restart
        # needs an external supervisor (k8s Job restartPolicy etc.) that
        # relaunches EVERY machine; recovery is then checkpoint-based
        # (ACCELERATE_RESTART_COUNT + load_state) like the single-machine
        # path.
        raise ValueError(
            "--max_restarts is per-machine and cannot coordinate a group "
            "restart across --num_machines > 1; restart the launcher on "
            "every machine (e.g. via your job scheduler) instead"
        )
    procs_per_machine = total // num_machines
    rank_base = getattr(args, "machine_rank", 0) * procs_per_machine

    def run_once(attempt):
        procs = []
        for local_rank in range(procs_per_machine):
            env = build_env(args, process_id=rank_base + local_rank, num_processes=total)
            env["ACCELERATE_RESTART_COUNT"] = str(attempt)
            cmd = [sys.executable, *_script_argv(args)]
            procs.append(subprocess.Popen(cmd, env=env))
        interval = getattr(args, "monitor_interval", None)
        interval = 5 if interval is None else interval
        rc = 0
        try:
            while procs:
                alive = []
                for p in procs:
                    code = p.poll()
                    if code is None:
                        alive.append(p)
                    elif code != 0:
                        # one rank died: the rest would hang on the next
                        # collective — terminate the group (torchelastic
                        # group-restart semantics)
                        rc = code
                        for q in procs:
                            if q.poll() is None:
                                q.terminate()
                        return rc
                procs = alive
                if procs:
                    time.sleep(min(interval, 1.0))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
        return rc

    return _supervised(run_once, args)


def pod_ssh_launcher(args) -> int:
    """SSH fan-out: each pod host re-invokes the launcher locally
    (reference tpu_pod_launcher: commands/launch.py:909-965). Honors
    ``--max_restarts`` like the local launchers: a failed fan-out is
    re-dispatched whole (every host restarts together — the surviving
    hosts' collectives would deadlock otherwise)."""
    hosts = [h.strip() for h in args.tpu_hosts.split(",") if h.strip()]
    coordinator = f"{hosts[0]}:{args.main_process_port or 7777}"
    # Pod hosts usually share the VM image / NFS checkout; keep the package
    # importable there too when it isn't pip-installed. ${PYTHONPATH:+:...}
    # avoids a trailing empty entry (= cwd) when the remote var is unset.
    import shlex

    script_cmd = " ".join(shlex.quote(a) for a in _script_argv(args))

    def run_once(attempt):
        procs = []
        for rank, host in enumerate(hosts):
            remote_cmd = (
                f"ACCELERATE_COORDINATOR_ADDRESS={coordinator} "
                f"ACCELERATE_NUM_PROCESSES={len(hosts)} ACCELERATE_PROCESS_ID={rank} "
                f"ACCELERATE_RESTART_COUNT={attempt} "
                f'PYTHONPATH={_pkg_root()}"${{PYTHONPATH:+:$PYTHONPATH}}" '
                f"{sys.executable} {script_cmd}"
            )
            target = f"{args.ssh_user}@{host}" if args.ssh_user else host
            procs.append(subprocess.Popen(["ssh", "-o", "StrictHostKeyChecking=no", target, remote_cmd]))
        rc = 0
        for p in procs:
            rc = p.wait() or rc
        return rc

    return _supervised(run_once, args)


def launch_command(args) -> int:
    args = _load_config_into_args(args)
    if (
        args.main_process_port is None
        and args.num_processes > 1
        and getattr(args, "num_machines", 1) == 1
        and getattr(args, "main_process_ip", "127.0.0.1") in ("127.0.0.1", "localhost")
    ):
        # resolve ONCE before the per-rank env fan-out (each rank must get
        # the same coordinator address); avoids collisions between
        # concurrent local groups on the fixed default port. Multi-machine
        # topologies keep the fixed default: every machine's launcher must
        # independently resolve the SAME coordinator port
        from ..utils.environment import get_free_port

        args.main_process_port = get_free_port()
    explicit = getattr(args, "_explicit", None) or set()
    # A topology request — CLI flag, or YAML value that DIFFERS from the
    # parser default — means the user is NOT asking for a bare pod fan-out.
    # Default-valued YAML keys must not count: the config wizard writes
    # num_machines: 1 unconditionally, which would otherwise disable pod
    # autodiscovery for everyone who ever ran `accelerate-tpu config`.
    topology_defaults = {
        "num_processes": 1,
        "num_machines": 1,
        "machine_rank": 0,
        "main_process_ip": "127.0.0.1",
    }
    requested = {"num_processes", "machine_rank", "main_process_ip", "num_machines"} & explicit
    for key in set(topology_defaults) & getattr(args, "_from_config", set()):
        if getattr(args, key) != topology_defaults[key]:
            requested.add(key)
    wants_local = bool(
        args.cpu
        or args.fake_devices
        or getattr(args, "no_pod_discovery", False)
        or requested
    )
    if not args.tpu_hosts and not wants_local:
        # bare `accelerate-tpu launch script.py` on a TPU pod: discover the
        # worker hostnames from the TPU runtime env / GCE metadata and fan
        # out from worker 0 (reference: tpu_pod_launcher autodiscovery)
        hosts = discover_pod_hosts()
        if hosts is not None:
            if pod_worker_id() != 0:
                print("launch: pod worker != 0 defers to worker 0's SSH fan-out")
                return 0
            args.tpu_hosts = ",".join(hosts)
    if args.tpu_hosts:
        return pod_ssh_launcher(args)
    num_machines = getattr(args, "num_machines", 1) or 1
    on_host_cpu = bool(args.cpu or args.fake_devices) or os.environ.get("JAX_PLATFORMS", "").startswith("cpu")
    if args.num_processes > num_machines and not on_host_cpu:
        # the chips of one host belong to ONE process: N local processes
        # would each ask for all of them, and all but the first fail or
        # hang. Spawning is the CPU fake-mesh path (notebook_launcher rule)
        if num_machines > 1:
            raise ValueError(
                f"--num_processes {args.num_processes} over --num_machines {num_machines} asks for more "
                "than one process per host; on an accelerator one process drives all local chips "
                "(use --num_processes equal to --num_machines, or --cpu/--fake_devices)"
            )
        print(
            f"launch: one process drives every local chip on an accelerator backend — "
            f"--num_processes {args.num_processes} ignored, running one process "
            "(pass --cpu or --fake_devices for the multi-process CPU fake mesh)",
            file=sys.stderr,
        )
        args.num_processes = 1
    if args.num_processes > 1 or num_machines > 1:
        # covers manual multi-machine (this launcher run once per machine
        # with --machine_rank): each invocation spawns its local share
        return multi_process_launcher(args)
    return simple_launcher(args)


def main():
    parser = launch_parser()
    args = parser.parse_args()
    raise SystemExit(launch_command(args))


if __name__ == "__main__":
    main()
