"""End-to-end system behaviour: train -> crash -> detectable restore ->
continue, with the PBComb checkpointer and seeded per-step batches;
elastic rescale mid-run; serving through a jitted batched function."""

import random
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.persist.checkpoint import PBCombCheckpointer
from repro.persist.store import MemStore
from repro.runtime.elastic import ElasticCoordinator

D_IN, D_OUT, BATCH = 8, 4, 16


def _batch(seed, step):
    """The deterministic data pipeline: step i's batch is a pure
    function of (seed, i), so a restored run replays the same data."""
    rng = np.random.default_rng((seed, step))
    return {"x": rng.standard_normal((BATCH, D_IN), dtype=np.float32),
            "y": rng.standard_normal((BATCH, D_OUT), dtype=np.float32)}


def _fresh_state():
    rng = np.random.default_rng(0)
    params = {"w": rng.standard_normal((D_IN, D_OUT), dtype=np.float32),
              "b": np.zeros((D_OUT,), np.float32)}
    opt = {k: np.zeros_like(v) for k, v in params.items()}   # momentum
    return params, opt


def _train_step(params, opt, batch, lr=np.float32(0.05),
                beta=np.float32(0.9)):
    """One float32 step of least squares with momentum SGD."""
    err = batch["x"] @ params["w"] + params["b"] - batch["y"]
    grads = {"w": batch["x"].T @ err / np.float32(BATCH),
             "b": err.mean(axis=0)}
    opt = {k: beta * opt[k] + grads[k] for k in params}
    params = {k: params[k] - lr * opt[k] for k in params}
    return params, opt


def test_train_crash_restore_continue():
    """The canonical recoverable-training loop:

    1. train 6 steps, checkpointing (announce + combine) every 2;
    2. crash (store adversarially drops unsynced data; process dies);
    3. recover: detectability tells the trainer exactly which step the
       durable state captured; the data pipeline resumes from it;
    4. continue to step 10 and verify the final state EXACTLY matches an
       uninterrupted run (bit-identical replay in f32).
    """
    store = MemStore()

    def pack_state(params, opt, step):
        return {"params": params, "opt": opt,
                "step": np.asarray(step, np.int32)}

    params, opt = _fresh_state()
    template = pack_state(params, opt, 0)
    ck = PBCombCheckpointer(store, 1, template)
    ck.initialize(pack_state(params, opt, 0))

    ann = 0                                  # consecutive announce seq
    for i in range(6):
        params, opt = _train_step(params, opt, _batch(1, i))
        if (i + 1) % 2 == 0:
            ann += 1
            ck.announce(0, pack_state(params, opt, i + 1), seq=ann,
                        response=i + 1)
            ck.combine_once()

    store.crash(random.Random(0))           # kill the job

    # ---- recovery ----
    ck2 = PBCombCheckpointer(store, 1, template)
    payload = ck2.recover()
    restore_step = int(payload["step"])
    assert restore_step in (0, 2, 4, 6)     # a committed round, never torn
    if restore_step:
        # detectability: the announce with seq=restore_step/2 took effect
        # and its logged response is the captured training step
        assert ck2.was_applied(0, restore_step // 2)
        assert ck2.response(0) == restore_step
    params2, opt2 = payload["params"], payload["opt"]
    for i in range(restore_step, 10):
        params2, opt2 = _train_step(params2, opt2, _batch(1, i))

    # ---- uninterrupted reference ----
    params_ref, opt_ref = _fresh_state()
    for i in range(10):
        params_ref, opt_ref = _train_step(params_ref, opt_ref, _batch(1, i))

    for k in params_ref:
        np.testing.assert_array_equal(params2[k], params_ref[k])
        np.testing.assert_array_equal(opt2[k], opt_ref[k])


def test_elastic_rescale_replays_from_committed_step():
    co = ElasticCoordinator(4, heartbeat_timeout=0.01)
    for h in range(4):
        co.heartbeat(h, step=7)
    time.sleep(0.02)
    for h in (0, 1, 2):
        co.heartbeat(h, step=8)
    plan = co.rescale(committed_step=6, failed=co.detect_failures())
    assert plan.dp_size == 3 and plan.restore_step == 6
    batches = [_batch(9, plan.restore_step) for _ in plan.hosts]
    for b in batches[1:]:
        np.testing.assert_array_equal(batches[0]["x"], b["x"])
        np.testing.assert_array_equal(batches[0]["y"], b["y"])


def test_serving_with_real_model():
    """The combining engine drives a jitted batched function: the decode
    combiner's batch IS one call over a fixed padded batch, the shape a
    compiled model would need."""
    from repro.serving.engine import CombiningEngine

    VOCAB = 1024
    FIXED_B = 4   # jit'd shapes are fixed; combiner batches are padded
    jit_next = jax.jit(lambda t: (t * 31 + 7) % VOCAB)

    def next_tokens(last):
        toks = list(last) + [0] * (FIXED_B - len(last))
        nxt = np.asarray(jit_next(jnp.asarray(toks, jnp.int32)))
        return [int(t) for t in nxt[:len(last)]]

    def prefill_batch(prompts):
        return next_tokens([p[-1] for p in prompts]), \
            list(range(len(prompts)))

    def decode_batch(kvs, last):
        return next_tokens(last)

    eng = CombiningEngine(4, prefill_batch_fn=prefill_batch,
                          decode_batch_fn=decode_batch, n_kv_slots=4,
                          max_batch=4, eos_token=-1)
    eng.start()
    results = {}
    barrier = threading.Barrier(4)

    def client(c):
        barrier.wait()                 # announce together -> one round
        results[c] = eng.submit(c, [c + 1, c + 2, c + 3], max_tokens=4,
                                seq=1, timeout=180)

    ts = [threading.Thread(target=client, args=(c,)) for c in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(180)
    eng.stop()
    assert not any(t.is_alive() for t in ts)
    assert len(results) == 4
    for c, r in results.items():
        want, tok = [], c + 3
        for _ in range(4):
            tok = (tok * 31 + 7) % VOCAB
            want.append(tok)
        assert r["tokens"] == want
