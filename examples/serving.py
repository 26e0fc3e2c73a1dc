"""Serving example: concurrent clients through the micro-batching engine.

Parity: BigDL 2.0's Cluster Serving quickstart (arXiv 2204.01715 §4) —
train a model with the training stack, then serve it to many concurrent
clients. Here the serving tier is in-process (`bigdl_tpu.serving`): train
a small classifier, `warmup()` the engine's shape buckets, fire N client
threads at it, and check the served outputs are bit-identical to offline
batch `LocalPredictor.predict` — then serve the weight-only int8
quantized copy (`nn/quantized.py`) through a second engine and report
latency percentiles and batching gauges for both.
"""

from __future__ import annotations

import os as _os
import sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import argparse
import threading

import numpy as np


def build_model(n_feat: int, n_class: int):
    import bigdl_tpu.nn as nn
    return (nn.Sequential(name="serving_mlp")
            .add(nn.Linear(n_feat, 64)).add(nn.Tanh())
            .add(nn.Linear(64, n_class)).add(nn.LogSoftMax()))


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--n", type=int, default=384)
    p.add_argument("--clients", type=int, default=8)
    p.add_argument("--requests", type=int, default=128)
    p.add_argument("--max-epoch", type=int, default=4)
    args = p.parse_args(argv)

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.nn.quantized import Quantizer
    from bigdl_tpu.optim.predictor import LocalPredictor
    from bigdl_tpu.serving import InferenceEngine

    # synthetic separable 3-class data, same recipe as the other examples
    rs = np.random.RandomState(7)
    n_feat, n_class = 12, 3
    Y = (rs.randint(0, n_class, size=args.n) + 1).astype(np.int32)
    X = rs.rand(args.n, n_feat).astype(np.float32) * 0.3
    for i in range(args.n):
        X[i, (Y[i] - 1) * 4:(Y[i] - 1) * 4 + 4] += 0.6

    model = build_model(n_feat, n_class)
    o = optim.Optimizer(model, (X, Y), nn.ClassNLLCriterion(),
                        batch_size=32, local=True)
    o.set_optim_method(optim.Adam(learning_rate=3e-3))
    o.set_end_when(optim.max_epoch(args.max_epoch))
    o.optimize()

    samples = [Sample(X[i]) for i in range(args.requests)]
    offline = LocalPredictor(model, batch_size=32).predict(samples)

    def serve(served_model, label, convert):
        eng = InferenceEngine(served_model, max_batch_size=32,
                              max_wait_ms=2.0, convert=convert)
        results = [None] * len(samples)
        try:
            eng.warmup(samples[0])
            per = len(samples) // args.clients

            def client(k):
                lo = k * per
                hi = len(samples) if k == args.clients - 1 else lo + per
                for i in range(lo, hi):
                    results[i] = eng.predict(samples[i], timeout=60)

            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(args.clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            stats = eng.stats()
        finally:
            eng.close()
        print(f"{label}: {stats['completed']} requests over "
              f"{stats['batches']} micro-batches "
              f"(p50 batch {stats.get('batch_size_p50')}), latency p50/p99 "
              f"{stats.get('latency_ms_p50')}/{stats.get('latency_ms_p99')}"
              f" ms, bucket hit rate {stats['bucket_hit_rate']}")
        return results

    served = serve(model, "fp32 engine", convert=True)
    # equal to offline predict to float32 rounding: a micro-batch's size
    # follows the clients' timing, and this backend's gemm is bit-equal
    # only at the same padded shape
    for i, row in enumerate(served):
        np.testing.assert_allclose(row, offline[i], rtol=0, atol=1e-6)

    q = Quantizer.quantize(model, weight_only=True)
    q_served = serve(q, "int8 (weight-only) engine", convert=False)
    preds = np.stack(served).argmax(1)
    q_preds = np.stack(q_served).argmax(1)
    agree = float((preds == q_preds).mean())
    acc = float((preds + 1 == Y[:len(preds)]).mean())
    print(f"served accuracy={acc:.3f}  int8 top-1 agreement={agree:.3f}")
    assert agree > 0.95, agree
    return acc


if __name__ == "__main__":
    main()
