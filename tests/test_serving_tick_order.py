"""What the host does between a decode pass and the next program (serving.py ``ServingEngine.step``):
a retirement's clear goes out behind the next tick's first program, all of a tick's in one call, and
always ahead of any paste into its slot and of the next decode tick; a fresh request's sampling chain,
``fold_in(key(seed), uid)``, is derived by the program that samples its first token."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.generation import generate
from accelerate_tpu.models import EvaByteConfig, JambaConfig, LlamaConfig, create_evabyte_model, create_jamba_model, create_llama_model
from accelerate_tpu.models.lfm2_moe import Lfm2MoeConfig, create_lfm2_moe_model
from accelerate_tpu.ops.paged_kv import STATE_LEAVES, clear_slot, clear_slots
from accelerate_tpu.scheduling import SchedulerConfig
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.telemetry.trace import phase_log

MODELS = {
    "dense": (lambda: create_llama_model(LlamaConfig.tiny(), seq_len=16), {"prompt_buckets": (8,), "max_len": 64, "tick_block": 4}),
    "paged": (lambda: create_llama_model(LlamaConfig.tiny(), seq_len=16), {"prompt_buckets": (8,), "max_len": 64, "tick_block": 4, "paged_block_size": 4}),
    "jamba": (lambda: create_jamba_model(JambaConfig.tiny(), seed=3, seq_len=16), {"prompt_buckets": (8,), "max_len": 64, "tick_block": 4, "paged_block_size": 8}),
    "lfm2": (lambda: create_lfm2_moe_model(Lfm2MoeConfig.tiny(), seed=3, seq_len=16), {"prompt_buckets": (8,), "max_len": 64, "tick_block": 4, "paged_block_size": 8}),
    # a window of 32 and chunks of 4: a request of 5 + 40 tokens closes a window and holds summary pages
    "eva": (lambda: create_evabyte_model(EvaByteConfig.tiny(), seed=3, seq_len=16), {"prompt_buckets": (8,), "max_len": 128, "tick_block": 8, "paged_block_size": 4}),
}


@pytest.fixture(scope="module")
def models():
    built = {}
    return lambda kind: built.setdefault(kind, MODELS[kind][0]())


@pytest.fixture(scope="module")
def llama(models):
    return models("paged")


def _ids(n, start=1, mul=7):
    return ((np.arange(n) * mul + start) % 250 + 3).astype(np.int32)


def _engine(models, kind, **kw):
    return ServingEngine(models(kind), **{"num_slots": 2, **MODELS[kind][1], **kw})


def _alone(models, kind, prompt, n, **kw):
    """Tokens and logprobs of one request served alone by a fresh engine: its slot was never anybody else's."""
    engine = _engine(models, kind, **kw)
    uid = engine.submit(prompt, max_new_tokens=n)
    engine.run()
    return engine.partial(uid), engine.logprobs(uid)


def _last_tick():
    return phase_log().roots("engine.tick")[-1]


def _step_until_retired(engine, uid, limit=40):
    for _ in range(limit):
        engine.step()
        if uid in engine.done:
            return
    raise AssertionError(f"request {uid} did not retire in {limit} ticks")


def _state_leaves(cache):
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    return [leaf for path, leaf in flat if str(getattr(path[-1], "key", path[-1])) in STATE_LEAVES]


@pytest.mark.parametrize("kind", list(MODELS))
def test_slot_retired_in_one_tick_and_admitted_in_the_next_serves_what_generate_does(models, kind):
    """Request A retires in tick N while B decodes on; C is submitted and takes A's slot in tick N + 1. In the
    paged layouts A's clear is pending between the two ticks (host books settled: blocks and, for EVA, summary
    pages back in the allocator), goes out in tick N + 1 behind C's prefill and ahead of its paste (a recurrent
    state is zero bit for bit when the paste runs), and C's tokens and logprobs are those of C served alone
    in a slot nobody had, and ``generate``'s."""
    new = {"eva": 40}.get(kind, 9)
    prompt_a, prompt_b, prompt_c = _ids(5), _ids(7, start=40), _ids(6, start=90, mul=11)
    engine = _engine(models, kind)
    free0 = engine.pool_free_blocks
    a = engine.submit(prompt_a, max_new_tokens=new)
    b = engine.submit(prompt_b, max_new_tokens=3 * new)
    _step_until_retired(engine, a)
    assert engine.slot_req[0] is None and engine.slot_req[1] is not None
    c = engine.submit(prompt_c, max_new_tokens=new)
    if engine.paged:
        assert engine._clear_pending == [0], "the walk settled the host's books and left the device's half pending"
        assert engine.pool_free_blocks == free0 - len(engine._slot_blocks[1]) - len(getattr(engine, "_slot_summary", [{}, {}])[1])
        seen, paste = [], engine._paste

        def checked_paste(cache, *args):
            seen.append((list(engine._clear_pending), [np.asarray(leaf[0]) for leaf in _state_leaves(cache)]))
            return paste(cache, *args)

        engine._paste = checked_paste
    engine.step()
    if engine.paged:
        (pending, states), = seen
        assert pending == [] and all(not s.any() for s in states), "the clear ran ahead of the paste: the slot's state is zero"
        assert kind not in ("jamba", "lfm2") or states, "the model has a recurrent state to look at"
        assert _last_tick().done["clears_deferred"] == 1 and engine.metrics.clears_deferred == 1
    else:
        assert _last_tick().done["clears_deferred"] == 0
    assert engine.slot_req[0] is not None and engine.slot_req[0].uid == c
    engine.run()
    want_toks, want_lps = _alone(models, kind, prompt_c, new)
    np.testing.assert_array_equal(engine.partial(c), want_toks)
    np.testing.assert_array_equal(engine.logprobs(c), want_lps)
    np.testing.assert_array_equal(
        engine.poll(c), np.asarray(generate(models(kind), jnp.asarray(prompt_c[None]), max_new_tokens=new))[0]
    )
    assert len(engine.partial(b)) == 3 * new and engine.pool_free_blocks == free0 and not engine._clear_pending


def test_three_retirements_of_one_tick_are_one_clear_program(llama):
    """Three requests end in the same tick and a fourth decodes on: the next tick, which admits nothing, sends
    one ``clear_slots`` ahead of its decode program, and ``clears_deferred`` reads 3 there and nowhere else."""
    engine = ServingEngine(llama, num_slots=4, prompt_buckets=(8,), max_len=64, tick_block=4, paged_block_size=4)
    calls, clear = [], engine._clear_slots

    def counted(cache, slots, n):
        calls.append((np.asarray(slots).tolist(), int(n)))
        return clear(cache, slots, n)

    engine._clear_slots = counted
    short = [engine.submit(_ids(4 + i, start=10 * i), max_new_tokens=5) for i in range(3)]
    long = engine.submit(_ids(6, start=70), max_new_tokens=20)
    engine.step()  # first tokens and four steps: the three are done
    assert all(u in engine.done for u in short) and not calls and sorted(engine._clear_pending) == [0, 1, 2]
    jax.block_until_ready(engine.slot_caches)  # the cache is alive: no program was handed it since the tick
    engine.step()
    assert calls == [([0, 1, 2, 0], 3)] and not engine._clear_pending
    done = _last_tick().done
    assert done["clears_deferred"] == 3 and done["admitted"] == 0 and engine.metrics.clears_deferred == 3
    engine.run()
    assert len(calls) == 2 and calls[1][1] == 1, "the last retirement leaves an idle engine: its clear goes at once"
    assert engine.metrics.clears_deferred == 3 and len(engine.partial(long)) == 20
    want = _alone(lambda kind: llama, "paged", _ids(6, start=70), 20, num_slots=4)
    np.testing.assert_array_equal(engine.partial(long), want[0])


def test_clear_slots_is_clear_slot_of_each_listed_slot_and_of_no_other(models):
    """``clear_slots(cache, slots, n)`` on a hybrid cache of ones: slots ``slots[:n]`` come out as ``clear_slot``
    leaves them, every other slot and the pools untouched, whatever stands past ``n``; ``n`` of 0 is the identity."""
    engine = _engine(models, "jamba", num_slots=4)
    ones = jax.tree.map(lambda l: jnp.ones(l.shape, l.dtype), engine.slot_caches)
    want = clear_slot(clear_slot(ones, jnp.int32(3)), jnp.int32(1))
    got = jax.jit(clear_slots)(ones, np.asarray([3, 1, 2, 2], np.int32), np.int32(2))
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    same = jax.jit(clear_slots)(ones, np.zeros((4,), np.int32), np.int32(0))
    assert all(np.asarray(l).all() for l in jax.tree.leaves(same))


def _pending_beside_a_decoding_request(llama, **kw):
    """An engine in which slot 0 has just retired (its clear pending) and slot 1 decodes on."""
    engine = ServingEngine(llama, num_slots=2, prompt_buckets=(8,), max_len=64, tick_block=4, paged_block_size=4, **kw)
    a = engine.submit(_ids(5), max_new_tokens=5)
    b = engine.submit(_ids(7, start=40), max_new_tokens=30)
    _step_until_retired(engine, a)
    assert engine._clear_pending == [0]
    calls, clear = [], engine._clear_slots

    def counted(cache, slots, n):
        calls.append(np.asarray(slots)[: int(n)].tolist())
        return clear(cache, slots, n)

    engine._clear_slots = counted
    return engine, b, calls


def test_cancel_sends_the_pending_clear_with_its_own(llama):
    engine, b, calls = _pending_beside_a_decoding_request(llama)
    free0 = engine._pcfg.num_blocks - 1
    engine.cancel(b)
    assert calls == [[0, 1]] and not engine._clear_pending and engine.pool_free_blocks == free0
    prompt = _ids(6, start=90)
    uid = engine.submit(prompt, max_new_tokens=6)
    engine.run()
    np.testing.assert_array_equal(engine.poll(uid), np.asarray(generate(llama, jnp.asarray(prompt[None]), max_new_tokens=6))[0])
    assert engine.metrics.clears_deferred == 0


def test_preemption_sends_the_pending_clear_and_the_resume_is_exact(llama):
    engine, b, calls = _pending_beside_a_decoding_request(llama, scheduler=SchedulerConfig(enable_preemption=True))
    engine._preempt(1)
    assert calls == [[0, 1]] and not engine._clear_pending
    engine.run()
    assert engine.metrics.decode_preemptions == 1 and engine.metrics.resumes == 1
    want = _alone(lambda kind: llama, "paged", _ids(7, start=40), 30)
    np.testing.assert_array_equal(engine.partial(b), want[0])
    np.testing.assert_allclose(engine.logprobs(b), want[1], atol=1e-5)  # recomputed rows: equal to the last place or two


def test_export_inflight_sends_the_pending_clear_first(llama):
    engine, b, calls = _pending_beside_a_decoding_request(llama)
    snaps = engine.export_inflight()
    assert calls == [[0]] and not engine._clear_pending and [s["uid"] for s in snaps] == [b]
    other = ServingEngine(llama, num_slots=2, prompt_buckets=(8,), max_len=64, tick_block=4, paged_block_size=4)
    uid = other.import_inflight(snaps[0])
    other.run()
    want = _alone(lambda kind: llama, "paged", _ids(7, start=40), 30)
    np.testing.assert_array_equal(other.partial(uid), want[0])


def test_a_hand_off_pastes_behind_the_pending_clear(llama):
    """``submit_prefilled``: no program of the admission stands ahead of its paste, so the pending clear is sent
    first and not counted as deferred; the request continues as a local one would."""
    engine, b, calls = _pending_beside_a_decoding_request(llama)
    prompt = _ids(6, start=90)
    prefiller = ServingEngine(llama, num_slots=1, prompt_buckets=(8,), max_len=64, tick_block=4)
    handoff = prefiller.prefill_detached(prompt, max_new_tokens=6, uid_key=engine._uid)
    uid = engine.submit_prefilled(handoff)
    engine.step()
    assert calls == [[0]] and _last_tick().done["clears_deferred"] == 0 and engine.slot_req[0].uid == uid
    engine.run()
    np.testing.assert_array_equal(engine.poll(uid), np.asarray(generate(llama, jnp.asarray(prompt[None]), max_new_tokens=6))[0])


# -- the sampling chain: made where it is used


def _key_bits(key):
    return np.asarray(jax.random.key_data(key)).tolist()


def test_a_fresh_requests_chain_is_fold_in_of_the_seed_and_the_uid_wherever_it_is_computed(llama):
    """The pair ``_request_key`` hands a program stands for ``fold_in(key(seed), uid)``; the prefill program
    given (the engine's key, the uid) samples the token and returns the chain it returns when given that
    chain itself with nothing to fold (the program as it was); a carried chain is handed on as it is."""
    engine = ServingEngine(llama, num_slots=2, prompt_buckets=(8,), max_len=64, temperature=0.9, seed=11)
    chain = jax.random.fold_in(jax.random.key(11), 5)
    key, fold = engine._request_key(5)
    assert key is engine._base_key and int(fold) == 5 and _key_bits(engine._chain_key(key, fold)) == _key_bits(chain)
    carried, no_fold = engine._request_key(5, carried=chain)
    assert carried is chain and int(no_fold) < 0 and engine._chain_key(carried, no_fold) is chain
    big = 2**31 + 3
    key, fold = engine._request_key(big)
    assert int(fold) < 0 and _key_bits(key) == _key_bits(jax.random.fold_in(jax.random.key(11), big))

    padded = np.zeros((1, 8), np.int32)
    padded[0, :6] = _ids(6)
    inside = engine._prefill[8](llama.params, padded, np.int32(6), engine._base_key, np.int32(5))
    outside = engine._prefill[8](llama.params, padded, np.int32(6), chain, np.int32(-1))
    assert int(inside[0]) == int(outside[0]) and float(inside[1]) == float(outside[1])
    assert _key_bits(inside[3]) == _key_bits(outside[3])
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(1, 8, llama.config.vocab_size)).astype(np.float32))
    a = engine._sample_at(logits, jnp.int32(3), engine._base_key, np.int32(5))
    b = engine._sample_at(logits, jnp.int32(3), chain, np.int32(-1))
    assert int(a[0]) == int(b[0]) and _key_bits(a[2]) == _key_bits(b[2])


def test_admission_of_a_fresh_request_runs_no_program_before_its_prefill(llama, monkeypatch):
    """``engine.admit`` derives nothing on the device: ``fold_in`` is not called between submission and the tick's end."""
    engine = ServingEngine(llama, num_slots=2, prompt_buckets=(8,), max_len=64, temperature=0.9, seed=11, paged_block_size=4)
    engine.generate_many([_ids(5)], max_new_tokens=3)  # the programs exist
    calls = []
    fold_in = jax.random.fold_in
    monkeypatch.setattr(jax.random, "fold_in", lambda *a, **k: calls.append(a) or fold_in(*a, **k))
    monkeypatch.setattr(jax.random, "key", lambda *a, **k: calls.append(a) or jax.random.PRNGKey(*a, **k))
    uid = engine.submit(_ids(6, start=20), max_new_tokens=6)
    engine.run()
    assert not calls and len(engine.partial(uid)) == 6


@pytest.mark.parametrize("paged", [None, 4], ids=["dense", "paged"])
def test_sampled_outputs_are_request_exact_across_a_preemption(llama, paged):
    """Temperature 0.9: a request evicted mid-decode and resumed carries its chain and ends with the tokens and
    logprobs it has when nobody evicts it; and the uid, not the slot or the tick, picks the chain: the same
    prompt under another uid samples another stream."""
    kw = {"num_slots": 1, "prompt_buckets": (8,), "max_len": 64, "tick_block": 2, "temperature": 0.9, "seed": 11, "paged_block_size": paged}
    victim_prompt, urgent_prompt = _ids(7), _ids(5, start=30)
    plain = ServingEngine(llama, **kw)
    v0 = plain.submit(victim_prompt, max_new_tokens=12)
    u0 = plain.submit(urgent_prompt, max_new_tokens=4)
    plain.run()
    engine = ServingEngine(llama, scheduler=SchedulerConfig(enable_preemption=True), **kw)
    victim = engine.submit(victim_prompt, max_new_tokens=12, priority=1)
    engine.step()
    engine.step()
    urgent = engine.submit(urgent_prompt, max_new_tokens=4, priority=0)
    engine.run()
    assert engine.metrics.decode_preemptions == 1 and engine.metrics.resumes == 1 and (victim, urgent) == (v0, u0)
    for got, want in ((victim, v0), (urgent, u0)):
        np.testing.assert_array_equal(engine.partial(got), plain.partial(want))
        # the resume recomputes the evicted rows by chunk windows: the same values to the last place or two
        np.testing.assert_allclose(engine.logprobs(got), plain.logprobs(want), atol=1e-5)
    again = plain.submit(victim_prompt, max_new_tokens=12)
    plain.run()
    assert plain.partial(again).tolist() != plain.partial(v0).tolist()


def test_the_walk_keeps_what_the_token_loop_kept(llama):
    """One pass a slot: a budget that ends inside a block, an eos inside a block (the rest of the block is
    overshoot) and a stop sequence (matched token by token) each keep exactly the tokens a walk token by
    token keeps, with their logprobs."""
    prompt = _ids(6)
    ref = np.asarray(generate(llama, jnp.asarray(prompt[None]), max_new_tokens=11))[0][len(prompt):]
    kw = {"num_slots": 2, "prompt_buckets": (8,), "max_len": 64, "tick_block": 4, "paged_block_size": 4}
    budget = ServingEngine(llama, **kw)
    uid = budget.submit(prompt, max_new_tokens=7)  # 1 + 4 + 2 of the second block
    budget.run()
    assert budget.partial(uid).tolist() == ref[:7].tolist() and len(budget.logprobs(uid)) == 7
    eos_at = next(i for i in range(2, 11) if ref[i] not in ref[:i])
    eos = ServingEngine(llama, eos_token_id=int(ref[eos_at]), **kw)
    uid = eos.submit(prompt, max_new_tokens=11)
    eos.run()
    assert eos.partial(uid).tolist() == ref[: eos_at + 1].tolist() and len(eos.logprobs(uid)) == eos_at + 1
    stop = ServingEngine(llama, **kw)
    uid = stop.submit(prompt, max_new_tokens=11, stop_sequences=[ref[eos_at - 1 : eos_at + 1].tolist()])
    stop.run()
    assert stop.partial(uid).tolist() == ref[: eos_at + 1].tolist() and len(stop.logprobs(uid)) == eos_at + 1
    np.testing.assert_array_equal(stop.logprobs(uid), eos.logprobs(uid))
