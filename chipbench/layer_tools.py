"""Arithmetic the per-layer readers share. A reader gets ``observed``: what the generator
recorded, the reduced trace under ``"trace"``, the configuration, its family's module under
``"family"``, the traffic and the device."""

from __future__ import annotations

from . import costs, stats
from .peaks import peaks_for


def idle_share(observed: dict):
    t = observed.get("trace")
    return None if not t else 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def _peaks(observed: dict) -> dict:
    return peaks_for(observed["device"]["kind"])


def decode_only_ticks(observed: dict) -> list:
    """Ticks in which the engine prefilled nothing and some slot decoded."""
    return [t for t in observed.get("ticks", ()) if t["prefills"] == 0 and t["decoding"] > 0 and t["start"] >= 0]


def decode_roofline_share(observed: dict):
    """Bytes a decode step must read (weights once, the live cache once) over the chip's
    memory bandwidth, over the step time measured: mean over the decode-only ticks."""
    ticks = decode_only_ticks(observed)
    if not ticks:
        return None
    cfg, family, k, bw = observed["config"], observed["family"], observed["tick_block"], _peaks(observed)["hbm_bytes_per_s"]
    shares = []
    for t in ticks:
        live = t["live_tokens"] - t["decoding"] * k / 2.0  # the tick's mean: a slot grows by k over it
        need = family.weight_bytes_per_decode_step(cfg, t["decoding"]) + \
            family.cache_bytes_per_decode_step(cfg, live, t["decoding"])
        shares.append(need / bw / ((t["end"] - t["start"]) / k))
    return 100.0 * sum(shares) / len(shares)


def kernel_seconds(observed: dict, needle: str):
    t = observed.get("trace")
    if not t:
        return None, 0.0
    names = [n for n in t["op_seconds"] if needle in n]
    return sum(t["op_seconds"][n] for n in names), sum(t["op_calls"][n] for n in names)


def paged_decode_attention_roofline(observed: dict):
    seconds, calls = kernel_seconds(observed, "paged_decode_attention")
    lo, hi = observed.get("traced") or (0.0, float("inf"))
    traced = [t for t in observed.get("ticks", ()) if t["decoding"] > 0 and t["end"] > lo and t["start"] < hi]
    if not seconds or not traced:
        return None
    k = observed["tick_block"]
    live = sum(t["live_tokens"] - t["decoding"] * k / 2.0 for t in traced) / len(traced)
    slots = sum(t["decoding"] for t in traced) / len(traced)
    heads, kv_heads, d = observed["family"].attention_shape(observed["config"])
    need = calls * costs.paged_decode_attention_bytes(heads * d, kv_heads * d, live, slots)
    return 100.0 * need / _peaks(observed)["hbm_bytes_per_s"] / seconds


def train_mfu(observed: dict):
    if not observed.get("step_ms"):
        return None
    seconds = stats.median(observed["step_ms"]) / 1e3
    return 100.0 * observed["flops_per_step"] / seconds / (observed["chips"] * _peaks(observed)["bf16_flops"])

