"""One cell, one process: ``python3 -m chipbench --workload <name> --seed <n> --seconds <s> --trace <0|1>``.

Reads the cell from ``BENCHMARK.json``, its configuration file, its traffic file, its
family, builder, generator and the readers of its per-layer metrics by name; makes the
weights on the device from the seed; warms the cell's own programs; measures for
``--seconds``; checks what the timed path produced against the plain reference; prints
one JSON object as its last line.
Without a TPU (or with fewer chips than the cell asks for) it exits non-zero and prints
no result. ``--rehearsal`` runs the same control flow on the host's CPU and says so.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse
import contextlib
import importlib.util
import json
import math
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 4.0


def load_manifest(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(manifest: dict, name: str) -> tuple:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in the manifest (known: {sorted(cells)})")
    cell = cells[name]
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    return cell, config


def find_file(manifest: dict, kind: str, name: str, suffixes) -> str:
    """``<path>/<kind>/<name><suffix>`` in the first of the manifest's ``paths`` that has it."""
    for base in manifest["paths"]:
        for suffix in suffixes:
            candidate = os.path.join(base if os.path.isabs(base) else os.path.join(ROOT, base), kind, name + suffix)
            if os.path.isfile(candidate):
                return candidate
    raise SystemExit(f"chipbench: no {kind} file named {name!r} under {manifest['paths']}")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(manifest: dict, kind: str, name: str):
    """The module ``<path>/<kind>/<name>.py``: a reader (``layers``), a family (``reference``), a builder
    or a generator. One module a file a process, so that what it has jitted is jitted once."""
    path = find_file(manifest, kind, name, (".py",))
    key = "chipbench_file_" + re.sub(r"\W", "_", path)
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        sys.modules[key] = module = importlib.util.module_from_spec(spec)  # dataclasses look their module up there
        spec.loader.exec_module(module)
    return sys.modules[key]


class Compiles:
    """Counts what jax asks its backend to compile, persistent-cache hits among them."""

    def __init__(self):
        import jax

        self.requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1


class Context:
    """What the harness hands a generator."""

    def __init__(self, args, manifest, cell, config, traffic, limits):
        self.seed, self.seconds, self.trace, self.control = args.seed, args.seconds, bool(args.trace), bool(args.control)
        self.manifest, self.cell, self.config, self.traffic, self._limits = manifest, cell, config, traffic, limits
        self.trace_seconds = TRACE_SECONDS
        self.trace_dir = os.path.join(ROOT, ".cache", "chipbench_trace", cell["name"])
        self.compiles = Compiles()
        self.warm_programs = self.window_open_at = None
        self._warm_mark = 0
        self._weights = None
        self._window_span = None

    # -- what a generator calls
    def say(self, what: str, **fields) -> None:
        print(json.dumps({"note": what, **fields}), flush=True)

    def family(self, *gives):
        """The module of the cell's family (``bench.reference``), which has to give every name in ``gives``."""
        name = self.config["bench"]["reference"]
        module = load(self.manifest, "reference", name)
        missing = [g for g in gives if not hasattr(module, g)]
        if missing:
            raise SystemExit(f"chipbench: the family {name!r} ({module.__file__}) gives no {', '.join(missing)}, "
                             f"which the cell {self.cell['name']!r} asks of it")
        return module

    def spec(self):
        return self.family("spec").spec(self.config)

    def _make(self, shardings=None, dtype=None):
        from . import weights

        return weights.make(self.spec(), self.seed, dtype or self.config["bench"]["param_dtype"], shardings)

    def build(self):
        builder = load(self.manifest, "builders", self.config["bench"]["builder"])

        def make_weights(shardings=None):
            import jax

            self._weights = self._make(shardings)
            jax.block_until_ready(self._weights)
            jax.clear_caches()  # the program that drew the weights is not the cell's: memory_peak leaves it out
            return self._weights

        return builder.build(self.config, self.traffic, self.seed, make_weights)

    def weights(self) -> dict:
        """The weights the builder was given (for a program that does not donate them)."""
        return self._weights

    def fresh_weights(self, dtype=None, shardings=None) -> dict:
        """The same weights made anew from the seed (a training step donates its own)."""
        self._weights = None
        return self._make(shardings, dtype)

    def end_warm_up(self) -> None:
        self.warm_programs = self._warm_mark = self.compiles.requests

    def compiles_since_warm_up(self):
        return argparse.Namespace(requests=self.compiles.requests - self._warm_mark)

    def window_opens(self, at: float) -> None:
        self.window_open_at = at

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start_trace(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the benchmark's own spans are enough; python frames slow the host loop
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._window_span = jax.profiler.TraceAnnotation("window")
        self._window_span.__enter__()

    def stop_trace(self) -> None:
        import jax

        self._window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def memory_peak(self) -> int:
        """The fullest chip's high-water mark. The allocator's ``peak_bytes_in_use`` counts
        buffers and leaves out what a running program takes for its temporaries (a program
        with 5.4 GB of them moved it by nothing: my chip run, PR 23), so the reading is the
        larger of that peak and the buffers in use now plus the largest temporary of a
        program loaded since the weights were made."""
        import jax

        devices = jax.local_devices()
        try:
            temp = max((e.get_compiled_memory_stats().temp_size_in_bytes
                        for e in devices[0].client.live_executables()), default=0)
        except Exception:  # a backend that does not report them
            temp = 0
        stats = [d.memory_stats() or {} for d in devices]
        return max(max(s.get("peak_bytes_in_use", 0), s.get("bytes_in_use", 0) + temp) for s in stats)

    def check(self, name: str, value: float, limit) -> dict:
        """A number compared and its limit. A number with no limit set has not passed."""
        return {"name": name, "value": value, "limit": limit, "ok": limit is not None and bool(value <= limit)}

    def limit(self, name: str):
        return self._limits.get(name)


def parse(argv):
    ap = argparse.ArgumentParser("chipbench", description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearsal", action="store_true", help="run on the host's CPU: control flow only, never a device result")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the lower-precision control's numbers (for setting limits; the driver never asks)")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override a traffic parameter (for the rate sweep; the driver never asks)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    manifest = load_manifest(args.manifest)
    cell, config_entry = find_cell(manifest, args.workload)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={cell['chips']}"
    elif "tpu" not in os.environ.get("JAX_PLATFORMS", "tpu"):
        raise SystemExit(f"chipbench: no accelerator: JAX_PLATFORMS={os.environ['JAX_PLATFORMS']}")
    sys.path.insert(0, ROOT)
    try:
        import accelerate_tpu  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"chipbench: the program is not beside the benchmark ({e})")

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearsal and device["platform"] != "tpu":
        raise SystemExit(f"chipbench: no accelerator: jax found {device}")
    if device["count"] < cell["chips"]:
        raise SystemExit(f"chipbench: the cell asks for {cell['chips']} chips and jax found {device}")
    if args.rehearsal:
        jax.config.update("jax_enable_compilation_cache", False)
        from accelerate_tpu.ops import paged_kv

        paged_kv.FORCE_KERNEL_INTERPRET = True  # the composition the chip runs, interpreted
    else:
        from accelerate_tpu.aot import configure_persistent_cache
        from .peaks import peaks_for

        peaks_for(device["kind"])  # an unknown device is an error before any work
        configure_persistent_cache()

    config_path = config_entry["file"]
    with open(config_path if os.path.isabs(config_path) else os.path.join(ROOT, config_path)) as f:
        config = json.load(f)
    with open(find_file(manifest, "traffic", cell["traffic"], (".json",))) as f:
        traffic = json.load(f)
    for item in args.set:
        key, _, value = item.partition("=")
        traffic[key] = json.loads(value)
    generator = load(manifest, "generators", traffic["generator"])
    ctx = Context(args, manifest, cell, config, traffic, traffic.get("limits", {}).get(cell["config"], {}))
    ctx.family(*generator.FAMILY_GIVES)  # a family without the path this cell takes fails here, before any weights
    ctx.say("start", workload=cell["name"], seed=args.seed, seconds=args.seconds, trace=args.trace, device=device)

    result = generator.run(ctx)

    for check in result["checks"]:
        print(json.dumps({"check": check["name"], "value": check["value"], "limit": check["limit"], "ok": check["ok"]}), flush=True)
    measured = dict(result["end_to_end"], setup_s=ctx.window_open_at - _T_START)
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"] + manifest["per_layer"]}
    device["memory_peak_bytes"] = result["memory_peak_bytes"]
    line = {"correct": all(c["ok"] for c in result["checks"]), "attempted": result["attempted"],
            "failed": result["failed"]}
    if not args.trace:
        wanted = [m["name"] for m in manifest["end_to_end"] if applies(m, cell["name"])]
        line["metrics"] = {n: {"value": measured[n], "unit": units[n]} for n in wanted}
    else:
        from . import trace
        from .peaks import UnknownDevice

        observed = dict(result["observed"], config=config, traffic=traffic, device=device, chips=cell["chips"],
                        warm_programs=ctx.warm_programs, end_to_end=measured, family=ctx.family())
        raw = trace.load(trace.newest_xplane(ctx.trace_dir), observed["spans"])
        observed["trace"] = reduced = trace.reduce(raw)
        metrics = {}
        for m in manifest["per_layer"]:
            if applies(m, cell["name"]):
                try:
                    value = load(manifest, "layers", m["name"]).read(observed)
                except UnknownDevice:
                    if not args.rehearsal:
                        raise
                    value = None  # the host's CPU has no peaks: a rehearsal reports no share of one
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        line["breakdown"] = trace.breakdown(reduced)
    line["device"] = device
    # every number compared beside its limit, last in the line and last on standard error
    line["compared"] = {c["name"]: {"value": c["value"] if math.isfinite(c["value"]) else repr(c["value"]),
                                    "limit": c["limit"]} for c in result["checks"]}
    print(json.dumps(line), flush=True)
    for name, c in line["compared"].items():
        print(f"chipbench: compared {name} {c['value']} limit {c['limit']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
