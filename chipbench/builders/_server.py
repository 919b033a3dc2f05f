"""What a serve builder hands to the ``open_loop_rounds`` generator, whatever model the engine serves."""

from __future__ import annotations

import gc


class Server:
    """The engine behind the four calls the ``open_loop_rounds`` generator makes."""

    def __init__(self, engine, config: dict):
        self.engine, self.config = engine, config
        self.tick_block = engine.tick_block

    def submit(self, prompt, new_tokens: int) -> int:
        return self.engine.submit(prompt, max_new_tokens=new_tokens)

    def step(self) -> None:
        self.engine.step()

    def tokens_so_far(self, uid: int):
        return self.engine.partial(uid)

    def finished(self, uid: int) -> bool:
        return self.engine.poll(uid) is not None

    def busy(self) -> bool:
        return bool(self.engine.queue) or self.engine.active_count > 0

    def counters(self) -> dict:
        m = self.engine.metrics
        return {"prefills": m.prefills, "queue_wait_ms": list(m.queue_wait_ms), "queue_len": len(self.engine.queue),
                "active": self.engine.active_count}

    def reset_counters(self) -> None:
        self.engine.metrics.queue_wait_ms.clear()

    def free(self) -> None:
        self.engine = None
        gc.collect()
