#!/usr/bin/env python
"""Reference-workload benchmarks that need no accelerator
(BASELINE.json configs 1 and 3):

- MNIST Keras CNN through ``HorovodRunner(np=-1)`` — the reference's
  canonical local-mode workload (reference ``runner_base.py:35-43``:
  np=-1 runs ``main`` in the driver for quick dev-loop iteration).
  BASELINE.md defines this config as single-process CPU.
- BERT-base fine-tune through the ``horovod.torch`` drop-in
  (reference workload family ``runner_base.py:35-45``; torch is
  CPU-only in this image, so this records the TORCH-PATH number — the
  point is the adapter path, batch/seq scaled to CPU budget).

One JSON line per workload, ``hardware`` recorded honestly. Synthetic
data everywhere: zero-egress sandboxes can't download MNIST/SQuAD, and
throughput doesn't care about pixel values.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _mnist_main():
    """Runs INSIDE HorovodRunner(np=-1): reference-style Keras CNN with
    the drop-in DistributedOptimizer + LogCallback wiring."""
    import numpy as np
    import tensorflow as tf

    import horovod.tensorflow.keras as hvd
    from sparkdl.horovod.tensorflow.keras import LogCallback

    hvd.init()
    tf.random.set_seed(42)
    model = tf.keras.Sequential([
        tf.keras.Input(shape=(28, 28, 1)),
        tf.keras.layers.Conv2D(32, 3, activation="relu"),
        tf.keras.layers.Conv2D(64, 3, activation="relu"),
        tf.keras.layers.MaxPooling2D(2),
        tf.keras.layers.Flatten(),
        tf.keras.layers.Dense(128, activation="relu"),
        tf.keras.layers.Dense(10),
    ])
    opt = hvd.DistributedOptimizer(tf.keras.optimizers.Adam(1e-3))
    model.compile(
        optimizer=opt,
        loss=tf.keras.losses.SparseCategoricalCrossentropy(
            from_logits=True),
    )
    rng = np.random.RandomState(0)
    n = 4096
    x = rng.rand(n, 28, 28, 1).astype("float32")
    y = rng.randint(0, 10, n).astype("int32")
    fit = dict(batch_size=64, verbose=0,
               callbacks=[
                   hvd.callbacks.BroadcastGlobalVariablesCallback(0),
                   LogCallback(),
               ])
    model.fit(x, y, epochs=1, **fit)      # trace + warm
    epochs = 3
    t0 = time.perf_counter()
    hist = model.fit(x, y, epochs=epochs, **fit)
    dt = time.perf_counter() - t0
    return {
        "metric": "mnist_keras_np-1_train_samples_per_sec",
        "value": round(n * epochs / dt, 1),
        "unit": "samples/sec",
        "hardware": "cpu (BASELINE.md defines np=-1 local mode as "
                    "single-process CPU)",
        "samples": n, "epochs": epochs, "batch": 64,
        "last_loss": round(float(hist.history["loss"][-1]), 4),
        "hvd_size": hvd.size(),
    }


def _bert_torch_main():
    """Runs INSIDE HorovodRunner(np=-1): BERT-base QA fine-tune step
    loop on the horovod.torch drop-in (DistributedOptimizer +
    broadcast_parameters), transformers random-init (zero egress)."""
    import numpy as np
    import torch
    from transformers import BertConfig, BertForQuestionAnswering

    import horovod.torch as hvd

    hvd.init()
    torch.manual_seed(0)
    cfg = BertConfig()  # BERT-base: 12L, 768d, 110M params
    model = BertForQuestionAnswering(cfg)
    model.train()
    batch, seq = 2, 128  # CPU budget; the config identity is the PATH
    opt = hvd.DistributedOptimizer(
        torch.optim.AdamW(model.parameters(), lr=3e-5),
        named_parameters=model.named_parameters(),
    )
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    rng = np.random.RandomState(0)
    ids = torch.from_numpy(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int64"))
    starts = torch.from_numpy(
        rng.randint(0, seq, (batch,)).astype("int64"))
    ends = torch.from_numpy(rng.randint(0, seq, (batch,)).astype("int64"))

    def step():
        opt.zero_grad()
        out = model(input_ids=ids, start_positions=starts,
                    end_positions=ends)
        out.loss.backward()
        opt.step()
        return float(out.loss.detach())

    step()  # warm
    n_steps = 3
    t0 = time.perf_counter()
    for _ in range(n_steps):
        loss = step()
    dt = time.perf_counter() - t0
    return {
        "metric": "bert_base_torch_hvd_train_samples_per_sec",
        "value": round(n_steps * batch / dt, 2),
        "unit": "samples/sec",
        "hardware": "cpu (torch is CPU-only in this image; records "
                    "the horovod.torch drop-in path)",
        "batch": batch, "seq": seq,
        "last_loss": round(loss, 4),
        "hvd_size": hvd.size(),
    }


def main():
    from sparkdl import HorovodRunner

    jobs = []
    if os.environ.get("SPARKDL_TPU_WORKLOAD") in (None, "", "mnist"):
        jobs.append(_mnist_main)
    if os.environ.get("SPARKDL_TPU_WORKLOAD") in (None, "", "bert"):
        jobs.append(_bert_torch_main)
    for job in jobs:
        try:
            # np=-1: reference local mode — main runs in this process
            print(json.dumps(HorovodRunner(np=-1).run(job)), flush=True)
        except Exception as e:
            print(json.dumps({"workload": job.__name__,
                              "error": str(e)[:300]}), flush=True)


if __name__ == "__main__":
    main()
