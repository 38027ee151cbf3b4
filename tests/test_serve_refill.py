"""Mid-stream refill isolation (ISSUE 10 satellite — the bug fix).

The old ``launch/serve.py`` prototype refilled free slots by re-running a
*whole-batch* prefill, overwriting the shared cache and corrupting every
in-flight request's KV state.  The promoted runner prefills batch-1 and
merges only the admitted slot's cache rows, so these tests pin, on the
real smoke model:

  * admitting a new request mid-decode leaves an in-flight slot's token
    stream bit-identical to a run where the admission never happened;
  * the cache merge touches exactly the admitted slot's rows (direct
    per-leaf comparison along the ``cache_batch`` axis);
  * the engine-level corollary: scheduled streams are independent of
    slot count.
"""

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.serve.runner import JaxModelRunner, snap_prompt_buckets
from repro.serve.scheduler import ServingEngine, TickClock
from repro.serve.traffic import make_traffic, scenario_preset

ARCH = "qwen3-14b"
MAX_LEN = 24


@pytest.fixture(scope="module")
def cfg():
    return smoke_config(ARCH)


def _prompt(seed: int, n: int, vocab: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, vocab, size=n).astype(np.int32)


def _decode_slot(runner: JaxModelRunner, streams: dict[int, list[int]],
                 steps: int) -> None:
    """Advance every stream in ``streams`` by ``steps`` batched decodes."""
    for _ in range(steps):
        last = np.zeros(runner.n_slots, np.int32)
        for slot, toks in streams.items():
            last[slot] = toks[-1]
        nxt = runner.decode(last)
        for slot in streams:
            streams[slot].append(int(nxt[slot]))


def test_mid_stream_admission_leaves_inflight_stream_unchanged(cfg):
    _check_admission_isolation(cfg)


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "granite-moe-1b-a400m"])
def test_moe_mid_stream_admission_leaves_inflight_stream_unchanged(arch):
    """The expert layer routes without capacity (models/moe.py), so a
    slot's tokens do not depend on which requests share its batch."""
    _check_admission_isolation(smoke_config(arch))


def _check_admission_isolation(cfg):
    pa = _prompt(0, 8, cfg.vocab_size)
    pb = _prompt(1, 8, cfg.vocab_size)

    # reference: request A alone, 6 decode steps
    solo = JaxModelRunner(cfg, n_slots=2, max_len=MAX_LEN)
    ref = {0: [solo.prefill(0, pa)]}
    _decode_slot(solo, ref, 6)

    # same model: A decodes 3 steps, then B is admitted into slot 1
    # mid-stream, then A decodes 3 more steps
    shared = JaxModelRunner(cfg, n_slots=2, max_len=MAX_LEN)
    streams = {0: [shared.prefill(0, pa)]}
    _decode_slot(shared, streams, 3)
    streams[1] = [shared.prefill(1, pb)]       # the mid-stream admission
    _decode_slot(shared, streams, 3)

    assert streams[0] == ref[0], (
        "admitting B mid-decode changed A's tokens — the whole-batch "
        "refill bug is back")
    # and B's stream matches B served alone from the same model state
    solo_b = JaxModelRunner(cfg, n_slots=2, max_len=MAX_LEN)
    ref_b = {1: [solo_b.prefill(1, pb)]}
    _decode_slot(solo_b, ref_b, 3)
    assert streams[1] == ref_b[1]


def test_cache_merge_touches_only_the_admitted_slots_rows(cfg):
    runner = JaxModelRunner(cfg, n_slots=3, max_len=MAX_LEN)
    runner.prefill(0, _prompt(0, 8, cfg.vocab_size))
    before = jax.tree.map(np.asarray, runner.cache)   # host copy

    runner.prefill(2, _prompt(2, 8, cfg.vocab_size))
    after = jax.tree.map(np.asarray, runner.cache)

    axes = runner.model.cache_axes()
    leaves, treedef = jax.tree_util.tree_flatten(before)
    leaves_after = treedef.flatten_up_to(after)
    leaves_axes = treedef.flatten_up_to(axes)
    touched = 0
    for b, a, ax in zip(leaves, leaves_after, leaves_axes):
        i = list(ax).index("cache_batch")
        # slot 0 (in-flight) and slot 1 (empty) rows are bit-identical
        np.testing.assert_array_equal(np.take(b, 0, axis=i),
                                      np.take(a, 0, axis=i))
        np.testing.assert_array_equal(np.take(b, 1, axis=i),
                                      np.take(a, 1, axis=i))
        if not np.array_equal(np.take(b, 2, axis=i), np.take(a, 2, axis=i)):
            touched += 1
    assert touched > 0            # the merge did write slot 2 somewhere


def test_engine_streams_independent_of_slot_count(cfg):
    sc = scenario_preset("steady", n_requests=4, prompt_buckets=(8,),
                         gen_buckets=(4,))
    trace = make_traffic(sc, seed=0)

    def serve(n_slots: int):
        runner = JaxModelRunner(cfg, n_slots=n_slots, max_len=sc.max_len)
        engine = ServingEngine(runner, n_slots=n_slots, clock=TickClock(0.01))
        return engine.run(trace, sc)

    r1, r3 = serve(1), serve(3)
    assert r1.streams == r3.streams
    assert set(r1.streams) == set(trace.rids)


def test_prefill_guards(cfg):
    runner = JaxModelRunner(cfg, n_slots=2, max_len=MAX_LEN)
    with pytest.raises(IndexError, match="slot"):
        runner.prefill(5, _prompt(0, 8, cfg.vocab_size))
    with pytest.raises(ValueError, match="max_len"):
        runner.prefill(0, _prompt(0, MAX_LEN, cfg.vocab_size))
    with pytest.raises(ValueError, match="token-LM"):
        JaxModelRunner(smoke_config("qwen2-vl-72b"), n_slots=2,
                       max_len=MAX_LEN)


def test_snap_prompt_buckets_rounds_to_ssm_chunk():
    dense = smoke_config(ARCH)
    assert snap_prompt_buckets(dense, (16, 8, 8, 32)) == (8, 16, 32)
    ssm = smoke_config("mamba2-2.7b")          # ssm_chunk == 8
    assert snap_prompt_buckets(ssm, (5, 8, 13)) == (8, 16)
    hybrid = smoke_config("zamba2-1.2b")
    assert snap_prompt_buckets(hybrid, (9,)) == (16,)
