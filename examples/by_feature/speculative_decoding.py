"""Speculative decoding: a draft model accelerates the target, token-exactly.

Run: python examples/by_feature/speculative_decoding.py
"""

from __future__ import annotations

import numpy as np


def main():
    from accelerate_tpu.generation import generate
    from accelerate_tpu.models import LlamaConfig, create_llama_model
    from accelerate_tpu.speculative import speculative_generate

    target = create_llama_model(LlamaConfig.tiny(), seed=0, seq_len=64)
    draft = create_llama_model(LlamaConfig.tiny(), seed=7, seq_len=64)

    ids = (np.arange(12) % 250).astype(np.int32)[None]
    want = np.asarray(generate(target, ids, max_new_tokens=24))
    got, stats = speculative_generate(
        target, draft, ids, max_new_tokens=24, gamma=4, return_stats=True
    )
    np.testing.assert_array_equal(np.asarray(got), want)
    print(
        f"token-exact; {stats['emitted']} tokens in {stats['target_forwards']} target "
        f"forwards ({stats['tokens_per_target_forward']:.2f} tok/forward, "
        f"accept rate {stats['accept_rate']:.2f})"
    )

    # perfect draft = the upper bound: gamma+1 tokens per target forward
    _, best = speculative_generate(
        target, target, ids, max_new_tokens=24, gamma=4, return_stats=True
    )
    print(f"perfect-draft bound: {best['tokens_per_target_forward']:.2f} tok/forward")

    print("speculative decoding example OK")


if __name__ == "__main__":
    main()
