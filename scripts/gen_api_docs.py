"""Generate the per-symbol API reference (docs/api/*.md) from docstrings.

The reference ships a full generated API doc site (docs/source/package_reference);
this is the equivalent for the TPU framework: deterministic markdown, one
file per module, signatures + docstrings for every public symbol. Re-run
after changing public surface:

    python scripts/gen_api_docs.py [--check]

``--check`` exits nonzero if the files on disk are stale (CI guard).
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT_DIR = os.path.join(REPO, "docs", "api")

MODULES = [
    "accelerate_tpu.accelerator",
    "accelerate_tpu.state",
    "accelerate_tpu.modeling",
    "accelerate_tpu.data_loader",
    "accelerate_tpu.optimizer",
    "accelerate_tpu.scheduler",
    "accelerate_tpu.generation",
    "accelerate_tpu.diffusion",
    "accelerate_tpu.serving",
    "accelerate_tpu.serving_programs",
    "accelerate_tpu.serving_fleet",
    "accelerate_tpu.serving_proc",
    "accelerate_tpu.serving_transport",
    "accelerate_tpu.scheduling",
    "accelerate_tpu.speculative",
    "accelerate_tpu.big_modeling",
    "accelerate_tpu.checkpointing",
    "accelerate_tpu.tracking",
    "accelerate_tpu.logging",
    "accelerate_tpu.launchers",
    "accelerate_tpu.local_sgd",
    "accelerate_tpu.parallel.mesh",
    "accelerate_tpu.parallel.sharding",
    "accelerate_tpu.parallel.pipeline",
    "accelerate_tpu.parallel.context",
    "accelerate_tpu.parallel.collectives",
    "accelerate_tpu.parallel.compression",
    "accelerate_tpu.parallel.zero",
    "accelerate_tpu.ops.attention",
    "accelerate_tpu.ops.flash_attention",
    "accelerate_tpu.ops.pallas_attention",
    "accelerate_tpu.ops.pallas_qmatmul",
    "accelerate_tpu.ops.kv_cache",
    "accelerate_tpu.ops.paged_kv",
    "accelerate_tpu.ops.pallas_paged_attention",
    "accelerate_tpu.ops.paged_walk",
    "accelerate_tpu.ops.pallas_latent_attention",
    "accelerate_tpu.ops.selective_scan",
    "accelerate_tpu.ops.pallas_selective_scan",
    "accelerate_tpu.ops.ssd_scan",
    "accelerate_tpu.ops.eva_attention",
    "accelerate_tpu.ops.pallas_ssd_step",
    "accelerate_tpu.ops.pallas_grouped_matmul",
    "accelerate_tpu.ops.moe",
    "accelerate_tpu.ops.fp8",
    "accelerate_tpu.ops.qdense",
    "accelerate_tpu.aot",
    "accelerate_tpu.aot.cache",
    "accelerate_tpu.aot.program_cache",
    "accelerate_tpu.aot.bucketing",
    "accelerate_tpu.ft.manifest",
    "accelerate_tpu.ft.manager",
    "accelerate_tpu.ft.preemption",
    "accelerate_tpu.ft.topology",
    "accelerate_tpu.ft.crashpoints",
    "accelerate_tpu.test_utils.fault_injection",
    "accelerate_tpu.utils.retry",
    "accelerate_tpu.utils.dataclasses",
    "accelerate_tpu.utils.operations",
    "accelerate_tpu.utils.lora",
    "accelerate_tpu.utils.quantization",
    "accelerate_tpu.utils.memory",
    "accelerate_tpu.utils.random",
    "accelerate_tpu.utils.offload",
    "accelerate_tpu.analysis.rules",
    "accelerate_tpu.analysis.ast_lint",
    "accelerate_tpu.analysis.jaxpr_lint",
    "accelerate_tpu.analysis.flightcheck",
    "accelerate_tpu.analysis.costmodel",
    "accelerate_tpu.analysis.perfmodel",
    "accelerate_tpu.analysis.perf_rules",
    "accelerate_tpu.analysis.numerics",
    "accelerate_tpu.analysis.numerics_rules",
    "accelerate_tpu.analysis.ranksim",
    "accelerate_tpu.analysis.divergence",
    "accelerate_tpu.analysis.searchspace",
    "accelerate_tpu.analysis.tuner",
    "accelerate_tpu.analysis.tune_rules",
    "accelerate_tpu.analysis.pipemodel",
    "accelerate_tpu.analysis.pipe_rules",
    "accelerate_tpu.analysis.hostsim",
    "accelerate_tpu.analysis.fleet_rules",
    "accelerate_tpu.analysis.kernelmodel",
    "accelerate_tpu.analysis.kernel_rules",
    "accelerate_tpu.analysis.changed",
    "accelerate_tpu.analysis.project_config",
    "accelerate_tpu.analysis.report",
    "accelerate_tpu.kernels",
    "accelerate_tpu.kernels.contracts",
    "accelerate_tpu.kernels.reference",
    "accelerate_tpu.telemetry",
    "accelerate_tpu.telemetry.eventlog",
    "accelerate_tpu.telemetry.step",
    "accelerate_tpu.telemetry.mfu",
    "accelerate_tpu.telemetry.serving_metrics",
    "accelerate_tpu.telemetry.summarize",
    "accelerate_tpu.telemetry.nonfinite",
    "accelerate_tpu.telemetry.wire",
    "accelerate_tpu.telemetry.trace",
    "accelerate_tpu.telemetry.flightrec",
    "accelerate_tpu.telemetry.critpath",
    "accelerate_tpu.telemetry.httpd",
    "accelerate_tpu.models",
]


def _sig(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # default-value reprs can embed memory addresses — strip for determinism
    return re.sub(r" at 0x[0-9a-fA-F]+", "", sig)


def _doc(obj) -> str:
    doc = inspect.getdoc(obj)
    if not doc:
        return "*(undocumented)*"
    # flax dataclass auto-docstrings embed default-object reprs w/ addresses
    return re.sub(r" at 0x[0-9a-fA-F]+", "", doc.strip())


def _public_members(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in vars(mod) if not n.startswith("_")]
    out = []
    for name in sorted(names):
        obj = getattr(mod, name, None)
        if obj is None or inspect.ismodule(obj):
            continue
        if inspect.isclass(obj) or callable(obj):
            # only classes/functions defined (or re-exported) by the package
            owner = getattr(obj, "__module__", "") or ""
            if not owner.startswith("accelerate_tpu"):
                continue
        elif not name.isupper():
            # plain values have no __module__; keep only CONSTANT_CASE ones
            continue
        out.append((name, obj))
    return out


def render_module(modname: str) -> str:
    mod = importlib.import_module(modname)
    lines = [f"# `{modname}`", ""]
    if mod.__doc__:
        lines += [inspect.cleandoc(mod.__doc__), ""]
    classes, functions, other = [], [], []
    for name, obj in _public_members(mod):
        if inspect.isclass(obj):
            classes.append((name, obj))
        elif callable(obj):
            functions.append((name, obj))
        else:
            other.append((name, obj))

    for name, obj in classes:
        lines += [f"## class `{name}{_sig(obj)}`", "", _doc(obj), ""]
        for mname, meth in sorted(vars(obj).items()):
            if mname.startswith("_"):
                continue
            # descriptors are NOT callable on CPython: unwrap them explicitly
            if isinstance(meth, property):
                if meth.fget is not None:
                    lines += [f"### `{name}.{mname}` *(property)*", "", _doc(meth.fget), ""]
                continue
            fn = meth.__func__ if isinstance(meth, (classmethod, staticmethod)) else meth
            if not (inspect.isfunction(fn) or inspect.ismethod(fn)):
                continue
            kind = " *(classmethod)*" if isinstance(meth, classmethod) else ""
            lines += [f"### `{name}.{mname}{_sig(fn)}`{kind}", "", _doc(fn), ""]
    for name, obj in functions:
        lines += [f"## `{name}{_sig(obj)}`", "", _doc(obj), ""]
    if other:
        lines += ["## Constants", ""]
        for name, obj in other:
            lines += [f"- `{name}`", ""]
    return "\n".join(lines).rstrip() + "\n"


# -- rules catalogue ------------------------------------------------------

CATALOGUE_PATH = os.path.join(REPO, "docs", "usage_guides", "static_analysis.md")
CATALOGUE_START = "<!-- rules-catalogue:start (generated by scripts/gen_api_docs.py — do not edit) -->"
CATALOGUE_END = "<!-- rules-catalogue:end -->"


def render_rules_catalogue() -> str:
    """The full TPU001-TPU405 rule table, generated from the
    ``analysis.rules`` registry so the doc cannot drift from the code."""
    from accelerate_tpu.analysis.rules import RULES

    lines = [
        "| ID | Name | Severity | Tier | Catches |",
        "|---|---|---|---|---|",
    ]
    for rid in sorted(RULES):
        r = RULES[rid]
        lines.append(f"| `{r.id}` | {r.name} | {r.severity} | {r.tier} | {r.summary} |")
    return "\n".join(lines)


def embed_rules_catalogue(check: bool) -> bool:
    """Splice the generated table between the catalogue markers in
    ``static_analysis.md``. Returns True when the file was already (or is
    now) up to date; False from --check when it is stale."""
    with open(CATALOGUE_PATH) as f:
        text = f.read()
    start = text.find(CATALOGUE_START)
    end = text.find(CATALOGUE_END)
    if start < 0 or end < 0:
        raise SystemExit(f"{CATALOGUE_PATH}: rules-catalogue markers missing")
    updated = text[: start + len(CATALOGUE_START)] + "\n" + render_rules_catalogue() + "\n" + text[end:]
    if check:
        return updated == text
    if updated != text:
        with open(CATALOGUE_PATH, "w") as f:
            f.write(updated)
    return True


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true", help="fail if docs on disk are stale")
    args = ap.parse_args()

    os.makedirs(OUT_DIR, exist_ok=True)
    index = ["# Generated API reference", "",
             "One page per module, generated by `scripts/gen_api_docs.py` — do not edit by hand.", ""]
    stale = []
    for modname in MODULES:
        content = render_module(modname)
        fname = modname.replace("accelerate_tpu.", "").replace(".", "_") + ".md"
        path = os.path.join(OUT_DIR, fname)
        index.append(f"- [`{modname}`]({fname})")
        if args.check:
            on_disk = open(path).read() if os.path.exists(path) else None
            if on_disk != content:
                stale.append(fname)
        else:
            with open(path, "w") as f:
                f.write(content)
    index_content = "\n".join(index) + "\n"
    index_path = os.path.join(OUT_DIR, "index.md")
    if args.check:
        if (not os.path.exists(index_path)) or open(index_path).read() != index_content:
            stale.append("index.md")
        if not embed_rules_catalogue(check=True):
            stale.append("usage_guides/static_analysis.md (rules catalogue)")
        if stale:
            print(f"STALE: {stale} — run python scripts/gen_api_docs.py", file=sys.stderr)
            raise SystemExit(1)
        print(f"api docs up to date ({len(MODULES)} modules)")
    else:
        with open(index_path, "w") as f:
            f.write(index_content)
        embed_rules_catalogue(check=False)
        print(f"wrote {len(MODULES) + 1} files to {OUT_DIR} (+ rules catalogue)")


if __name__ == "__main__":
    main()
