"""Serve a jitted next-token function with the combining batch engine:
concurrent clients, continuous batching (= software combining), priority
admission, and a crash with detectable request recovery.

The "model" is a stub, next token = (token * 31 + 7) % VOCAB, jitted
over a fixed padded batch: the engine takes any batched prefill and
decode functions, and the stub keeps the walkthrough about combining.

Run:  PYTHONPATH=src python examples/serve_combining.py
"""

import sys
import threading
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import CombiningRuntime
from repro.serving.engine import CombiningEngine

FIXED_B = 4
VOCAB = 1024


def main():
    # jit'd shapes are fixed; combiner batches are padded to FIXED_B
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

    # The engine announces through a shared CombiningRuntime: the same
    # board/recovery plumbing every recoverable structure uses.
    rt = CombiningRuntime(n_threads=FIXED_B)
    eng = CombiningEngine(FIXED_B, prefill_batch_fn=prefill_batch,
                          decode_batch_fn=decode_batch,
                          n_kv_slots=FIXED_B, max_batch=FIXED_B,
                          eos_token=-1, runtime=rt)
    eng.start()

    results = {}
    barrier = threading.Barrier(FIXED_B)

    def client(c):
        barrier.wait()
        results[c] = eng.submit(c, [c + 1, c + 2, c + 3], max_tokens=8,
                                seq=1, priority=float(c), timeout=300)

    ts = [threading.Thread(target=client, args=(c,))
          for c in range(FIXED_B)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    el = time.perf_counter() - t0
    for c in sorted(results):
        print(f"client {c}: tokens={results[c]['tokens']}")
    s = eng.stats
    print(f"\n{FIXED_B} requests in {el:.2f}s — "
          f"prefill rounds {s['prefill_rounds']} "
          f"(batched {s['prefill_batched']}), decode rounds "
          f"{s['decode_rounds']} (batched {s['decode_batched']}); "
          f"combining degree "
          f"{s['decode_batched'] / max(1, s['decode_rounds']):.1f}")

    # ---- crash + detectable request recovery -------------------------
    eng.restart_after_crash()
    r = eng.recover_request(0, [1, 2, 3], 8, seq=1)
    assert r == results[0]
    print("after crash: client 0's request recovered from the durable "
          "response log (no recomputation):", r["tokens"])
    eng.stop()


if __name__ == "__main__":
    main()
