"""Benchmark driver artifact.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

One process per chip: this parent imports no JAX and runs every
measurement in a child process of its own, one after the other, each with
a hard timeout — a chip belongs to one process at a time, so a parent that
had touched JAX would starve its children. Device rows need the chip: a
child asked for the device that finds no TPU exits non-zero, and so does
the whole run (and ``--rows``), with the child's diagnostic on stderr —
nothing is re-measured on the host under the same metric names.
``python bench.py measure <row> cpu`` is the explicit CPU spelling (sized-
down arguments, platform forced to the CPU) that ``tools/ci.sh`` uses.
Each child turns on the persistent compile cache (``core/env.py``).

Round-5 measurement discipline (VERDICT r4 asks 1-4):
  * EVERY timed row is the MEDIAN of >= 3 repetitions, with
    ``spread: {min, max, n}`` archived in the row (same unit as the value)
    and all MFU gates applied to the median.
  * Timed regions end with ONE host fence (D2H fetch of a result-dependent
    scalar, ``_host_fence``) amortized over the whole rep.
  * The conv roofline is measured on ResNet-50's OWN hot conv shapes
    (exact table derived from the zoo graph, batch-matched), FLOPs-weighted
    into a single achievable ceiling — not a single arbitrary conv.
  * ``bert_tf_import_train`` is the literal BASELINE.json:10 metric:
    import -> convert_to_variables -> sd.fit, full-graph HLO, tokens/s.
  * ``resnet50_e2e_fit`` trains from DECODED FILES through the uint8
    zero-host-math pipeline with on-device augmentation, to compare
    against the synthetic-data step rate.
"""

import json
import os
import statistics
import subprocess
import sys
import time

MEASURE_TIMEOUT_S = 1500
REPEATS = 3  # median-of-N for every timed row


# --------------------------------------------------------------------------
# measurements (run inside child processes)
# --------------------------------------------------------------------------

def _host_fence(tree) -> float:
    """End a timed region by materializing ON HOST a scalar that
    data-depends on ``tree``.

    Each training step is one jitted program whose outputs all complete
    together, and step N's params depend on step N-1's, so summing one
    leaf of the final params transitively fences the whole timed chain.
    On the local TPU v5e (libtpu 0.0.34) ``jax.block_until_ready`` does
    wait: over a chain of 200 4096² bf16 matmuls it measured 0.1442 s
    against 0.1446 s for a host fetch, and a fenced dispatch costs ~0.6 ms
    (``chip_smoke.py`` env_facts, PR 21) — so this fetch and
    ``block_until_ready`` are interchangeable there; the fetch stays
    because it cannot be wrong on any backend."""
    import jax
    import jax.numpy as jnp

    leaf = jax.tree_util.tree_leaves(tree)[0]
    return float(jnp.sum(jnp.asarray(leaf, jnp.float32)))


def _fence_tree(tree) -> None:
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        _host_fence(leaf)


def _median_rate(run_block, units_per_block: float, repeats: int = REPEATS):
    """``run_block()`` -> seconds for one fenced block of work. Returns
    (median_rate, spread_dict) with min/max expressed as RATES."""
    rates = []
    for _ in range(repeats):
        sec = run_block()
        rates.append(units_per_block / sec)
    return statistics.median(rates), {
        "min": round(min(rates), 2), "max": round(max(rates), 2),
        "n": repeats,
    }


def measure_lenet(batch: int = 256, warmup_iters: int = 12,
                  bench_iters: int = 60) -> dict:
    """LeNet-MNIST MultiLayerNetwork.fit() smoke row (BASELINE.json:7)."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator
    from deeplearning4j_tpu.data.mnist import MnistDataSetIterator
    from deeplearning4j_tpu.model.zoo import LeNet

    model = LeNet(seed=42).init()
    base = MnistDataSetIterator(batch, train=True, num_examples=batch * 8)
    data = DataSet.merge(list(base))

    def run(n_iters: int) -> float:
        epochs = max(1, n_iters // 8)
        it = ListDataSetIterator(data, batch)
        _host_fence(model.params)  # drain pending work
        start = time.perf_counter()
        model.fit(it, epochs=epochs)
        _host_fence(model.params)
        return time.perf_counter() - start

    run(warmup_iters)
    rate, spread = _median_rate(
        lambda: run(bench_iters), batch * max(1, bench_iters // 8) * 8)
    return {"samples_per_sec": rate, "spread": spread, "batch": batch}


def measure_resnet50(batch: int = 64, warmup_iters: int = 3,
                     bench_iters: int = 20,
                     compute_dtype: str = "bfloat16") -> dict:
    """ResNet-50 synthetic-ImageNet train samples/sec/chip + MFU
    (BASELINE.md row 1; the reference's ComputationGraph.fit path)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.bench.flops import resnet50_train_flops_per_example
    from deeplearning4j_tpu.bench.peak import chip_peak_flops
    from deeplearning4j_tpu.model.zoo import ResNet50
    from deeplearning4j_tpu.train.graph_solver import GraphSolver

    cd = None if compute_dtype in (None, "float32") else compute_dtype
    model = ResNet50(seed=42, num_classes=1000, compute_dtype=cd).init()
    solver = GraphSolver(model)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 3, 224, 224), model.dtype)
    y_np = np.zeros((batch, 1000), np.float32)
    y_np[np.arange(batch), rng.randint(0, 1000, batch)] = 1.0
    y = jnp.asarray(y_np)

    for _ in range(warmup_iters):
        solver.fit_batch((x,), (y,))
    _host_fence(model.params)

    def block():
        start = time.perf_counter()
        for _ in range(bench_iters):
            solver.fit_batch((x,), (y,))
        _host_fence(model.params)
        return time.perf_counter() - start

    sps, spread = _median_rate(block, batch * bench_iters)
    flops_per_ex = resnet50_train_flops_per_example()
    achieved = sps * flops_per_ex
    peak = chip_peak_flops(jax.devices()[0], compute_dtype)
    return {
        "samples_per_sec": sps,
        "spread": spread,
        "batch": batch,
        "compute_dtype": compute_dtype,
        "step_ms": batch / sps * 1e3,
        "model_tflops_per_sec": achieved / 1e12,
        "mfu": (achieved / peak) if peak else None,
    }


def measure_resnet50_b128() -> dict:
    """Batch-scaling probe: larger per-chip batch lifts conv MFU on v5e."""
    return measure_resnet50(batch=128, warmup_iters=3, bench_iters=15)


def measure_resnet50_e2e_fit(batch: int = 128, n_images: int = 512,
                             raw: int = 256, out: int = 224,
                             bench_steps: int = 12) -> dict:
    """End-to-end ResNet-50 training FROM FILES (VERDICT r4 ask 2's 'done'
    row): ppm files on disk -> uint8 decode (header parse + frombuffer
    views, zero per-pixel host math) -> async prefetch + device_put of raw
    bytes -> jitted ON-DEVICE augment (random crop + flip + NCHW + f32/255)
    -> ComputationGraph train step. Compare samples/sec against the
    synthetic-data row: the gap is the real input-pipeline cost."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.data.image_transform import (
        batch_random_crop, batch_random_flip,
    )
    from deeplearning4j_tpu.data.iterators import (
        AsyncDataSetIterator, MappedDataSetIterator, device_put_dataset,
    )
    from deeplearning4j_tpu.data.records import (
        ImageRecordReader, RecordReaderDataSetIterator,
    )
    from deeplearning4j_tpu.model.zoo import ResNet50
    from deeplearning4j_tpu.train.graph_solver import GraphSolver

    tmp = tempfile.mkdtemp(prefix="bench_e2e_")
    try:
        rng = np.random.RandomState(0)
        header = f"P6 {raw} {raw} 255\n".encode()
        n_classes = 8
        for c in range(n_classes):
            os.makedirs(os.path.join(tmp, f"c{c}"), exist_ok=True)
        for i in range(n_images):
            body = rng.randint(0, 256, (raw, raw, 3), np.uint8).tobytes()
            with open(os.path.join(tmp, f"c{i % n_classes}", f"{i}.ppm"),
                      "wb") as f:
                f.write(header + body)

        model = ResNet50(seed=42, num_classes=n_classes,
                         compute_dtype="bfloat16").init()
        # donate_inputs: every batch is a fresh prefetch-thread device_put,
        # so XLA reuses the input HBM across steps (ISSUE 7)
        solver = GraphSolver(model, donate_inputs=True)
        key = jax.random.PRNGKey(0)

        def prep(features):  # [b, raw, raw, 3] u8 -> [b, 3, out, out] f32
            x = jnp.transpose(jnp.asarray(features), (0, 3, 1, 2))
            x = x.astype(jnp.float32) * (1.0 / 255.0)
            x = batch_random_crop(x, key, out, out)
            return batch_random_flip(x, key)

        prep_j = jax.jit(prep)

        def make_iter():
            reader = ImageRecordReader(raw, raw, 3, root=tmp,
                                       output_dtype="uint8")
            base = RecordReaderDataSetIterator(
                reader, batch_size=batch, label_index=1,
                num_classes=n_classes)
            return MappedDataSetIterator(
                AsyncDataSetIterator(base, device_put_fn=device_put_dataset,
                                     device_buffers=2),
                feature_fn=prep_j)

        # warmup: compile prep + train step, warm the page cache; consume
        # the FULL pass so the async worker's in-flight device_put
        # transfers (H2D is the wall here) finish before the clock starts
        trained = 0
        for ds in make_iter():
            if ds.features.shape[0] == batch and trained < 2:
                solver.fit_batch((ds.features,), (ds.labels,))
                trained += 1
            _host_fence(ds.features)  # wait out the prefetched transfer
        _host_fence(model.params)

        def block():
            steps = 0
            start = time.perf_counter()
            while steps < bench_steps:
                for ds in make_iter():
                    if ds.features.shape[0] != batch:
                        continue
                    solver.fit_batch((ds.features,), (ds.labels,))
                    steps += 1
                    if steps >= bench_steps:
                        break
            _host_fence(model.params)
            return time.perf_counter() - start

        rate, spread = _median_rate(block, batch * bench_steps)

        # samples_per_sec_excl_transfer_wall (ISSUE 7 satellite): one
        # profiled pass attributes the step to data_wait/h2d/compute/host;
        # the projected rate with the input wall removed comes from the
        # StepProfiler breakdown, not from a bandwidth model.
        from deeplearning4j_tpu.obs import MetricsRegistry, StepProfiler

        prof = StepProfiler(sync_every=3, registry=MetricsRegistry())
        solver.profiler = prof  # same solver: the step stays compiled
        try:
            steps = 0
            while steps < max(bench_steps // 2, 2):
                for ds in prof.wrap_iterator(make_iter()):
                    if ds.features.shape[0] != batch:
                        continue
                    solver.fit_batch((ds.features,), (ds.labels,))
                    steps += 1
                    if steps >= max(bench_steps // 2, 2):
                        break
        finally:
            solver.profiler = None
        _host_fence(model.params)
        excl_rate = prof.samples_per_sec_excl_input(batch)
        prof_stats = prof.stats()

        # H2D bandwidth probe: says whether the from-files rate is bound
        # by the transfer or by the pipeline, and what the rate would be
        # were the transfer free (host decode + device compute overlap via
        # the async iterator). The local v5e host moved one 256 MB array
        # at 5.6 GB/s (chip_smoke.py env_facts, PR 21).
        probe = np.random.RandomState(1).randint(
            0, 256, (16_000_000,), np.uint8)  # 16 MB exactly (not MiB)
        jax.device_put(probe)
        bws = []
        for _ in range(3):
            start = time.perf_counter()
            d = jax.device_put(np.ascontiguousarray(probe))
            _host_fence(d)  # result-dependent: sums the transferred bytes
            bws.append(16.0 / (time.perf_counter() - start))
        h2d_mb_s = statistics.median(bws)
        bytes_per_img = raw * raw * 3
        transfer_s_per_img = bytes_per_img / (h2d_mb_s * 1e6)
        return {
            "samples_per_sec": rate, "spread": spread, "batch": batch,
            "n_images": n_images, "raw_size": raw, "crop": out,
            "h2d_bandwidth_mb_s": round(h2d_mb_s, 1),
            "transfer_bound": transfer_s_per_img > 1.0 / max(rate, 1e-9) * 0.5,
            # from the profiled pass: batch / (compute + host per-step) —
            # the rate this host/device pair reaches once the input wall
            # (data_wait + h2d) is fully overlapped
            "samples_per_sec_excl_transfer_wall": round(excl_rate, 1)
            if excl_rate else None,
            "profiled_phase_share": prof_stats["share"],
            "profiled_input_bound_share": prof_stats["input_bound_share"],
            "pipeline": "sharded u8 files -> worker decode -> async "
                        "device_put at enqueue (2-deep device ring) -> "
                        "on-device crop/flip/normalize -> donated train "
                        "step (host touches no float pixel)",
            "note": "transfer_bound compares the measured H2D bandwidth "
                    "with the bytes one image needs at the measured rate",
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def measure_bert(batch: int = 16, seq: int = 128, warmup_iters: int = 3,
                 bench_iters: int = 20,
                 compute_dtype: str = "bfloat16") -> dict:
    """BERT-base-shaped encoder train tokens/sec + MFU (BASELINE.md row 2)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.bench.flops import bert_train_flops_per_token
    from deeplearning4j_tpu.bench.peak import chip_peak_flops
    from deeplearning4j_tpu.model.zoo import BertEncoder
    from deeplearning4j_tpu.train.graph_solver import GraphSolver

    cd = None if compute_dtype in (None, "float32") else compute_dtype
    bert = BertEncoder(seed=42, compute_dtype=cd)
    model = bert.init()
    solver = GraphSolver(model)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, bert.vocab_size, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, bert.vocab_size, (batch, seq)),
                         jnp.int32)

    for _ in range(warmup_iters):
        solver.fit_batch((ids,), (labels,))
    _host_fence(model.params)

    def block():
        start = time.perf_counter()
        for _ in range(bench_iters):
            solver.fit_batch((ids,), (labels,))
        _host_fence(model.params)
        return time.perf_counter() - start

    tokens_per_sec, spread = _median_rate(block, batch * seq * bench_iters)
    flops_per_tok = bert_train_flops_per_token(bert, seq)
    achieved = tokens_per_sec * flops_per_tok
    peak = chip_peak_flops(jax.devices()[0], compute_dtype)
    return {
        "tokens_per_sec": tokens_per_sec,
        "spread": spread,
        "batch": batch,
        "seq": seq,
        "compute_dtype": compute_dtype,
        "step_ms": batch * seq / tokens_per_sec * 1e3,
        "model_tflops_per_sec": achieved / 1e12,
        "mfu": (achieved / peak) if peak else None,
    }


def measure_bert_b64() -> dict:
    """Batch-scaling probe: b=16 is dispatch/latency-bound on this chip."""
    return measure_bert(batch=64, warmup_iters=2, bench_iters=10)


def measure_lstm(batch: int = 32, seq: int = 200, vocab: int = 77,
                 hidden: int = 200, warmup_iters: int = 2,
                 bench_iters: int = 10) -> dict:
    """GravesLSTM char-RNN train chars/sec (BASELINE.json:9)."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.model.zoo import TextGenerationLSTM
    from deeplearning4j_tpu.train.solver import Solver

    model = TextGenerationLSTM(vocab_size=vocab, hidden=hidden, seed=42,
                               tbptt_length=50).init()
    solver = Solver(model)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, seq + 1))
    eye = np.eye(vocab, dtype=np.float32)
    x = jnp.asarray(eye[ids[:, :-1]].transpose(0, 2, 1))  # [b, vocab, t]
    y = jnp.asarray(eye[ids[:, 1:]].transpose(0, 2, 1))

    for _ in range(warmup_iters):
        solver.fit_batch(x, y)
    _host_fence(model.params)

    def block():
        start = time.perf_counter()
        for _ in range(bench_iters):
            solver.fit_batch(x, y)
        _host_fence(model.params)
        return time.perf_counter() - start

    rate, spread = _median_rate(block, batch * seq * bench_iters)
    return {
        "chars_per_sec": rate, "spread": spread,
        "batch": batch, "seq": seq, "vocab": vocab, "hidden": hidden,
        "step_ms": batch * seq / rate * 1e3,
        "model": "TextGenerationLSTM (GravesLSTM x2, peepholes, TBPTT 50)",
    }


def _frozen_bert(batch, seq, hidden, layers, heads, vocab):
    import tensorflow as tf
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2,
    )
    from transformers import BertConfig, TFBertModel

    cfg = BertConfig(
        vocab_size=vocab, hidden_size=hidden, num_hidden_layers=layers,
        num_attention_heads=heads, intermediate_size=hidden * 4,
        max_position_embeddings=512,
    )
    model = TFBertModel(cfg)

    @tf.function
    def fwd(input_ids):
        return model(input_ids, training=False).last_hidden_state

    cf = fwd.get_concrete_function(tf.TensorSpec((batch, seq), tf.int32))
    return convert_variables_to_constants_v2(cf)


def measure_bert_import(batch: int = 16, seq: int = 128, warmup_iters: int = 2,
                        bench_iters: int = 10, hidden: int = 768,
                        layers: int = 12, heads: int = 12,
                        vocab: int = 30522) -> dict:
    """BASELINE.json:10, inference leg: BERT-base via SameDiff TF import,
    full-graph HLO compile, inference tokens/sec."""
    import numpy as np

    try:
        frozen = _frozen_bert(batch, seq, hidden, layers, heads, vocab)
    except Exception as e:  # pragma: no cover - env-dependent
        return {"error": f"tf/transformers unavailable: {e}"}

    from deeplearning4j_tpu.samediff.tf_import import TFGraphMapper

    gd = frozen.graph.as_graph_def()
    in_name = frozen.inputs[0].name.split(":")[0]
    out_name = frozen.outputs[0].name.split(":")[0]

    sd = TFGraphMapper.import_graph(gd, outputs=[out_name])
    ids = np.random.default_rng(0).integers(0, vocab, (batch, seq)).astype(
        np.int32)
    compiled = sd.compile({in_name: ids}, [out_name])
    values = dict(sd._values)

    def step():
        return compiled(values, {in_name: ids})[out_name]

    out = None
    for _ in range(warmup_iters):
        out = step()
    _host_fence(out)

    def block():
        start = time.perf_counter()
        o = None
        for _ in range(bench_iters):
            o = step()
        _host_fence(o)
        return time.perf_counter() - start

    rate, spread = _median_rate(block, batch * seq * bench_iters)
    return {
        "tokens_per_sec": rate, "spread": spread,
        "batch": batch, "seq": seq,
        "step_ms": batch * seq / rate * 1e3,
        "model": f"TF-imported BERT-base (L={layers}, H={hidden}, "
                 f"vocab={vocab})",
        "mode": "inference full-graph HLO",
    }


def measure_bert_import_train(batch: int = 16, seq: int = 128,
                              bench_iters: int = 16, hidden: int = 768,
                              layers: int = 12, heads: int = 12,
                              vocab: int = 30522) -> dict:
    """THE literal BASELINE.json:10 metric (VERDICT r4 ask 4): SameDiff
    BERT *training* via TF import — import the frozen graph, convert the
    imported constants to trainable variables, attach a classification
    head, and time ``sd.fit`` (one full-graph HLO train step: loss + grads
    through all imported encoder weights + Adam). tokens/sec."""
    import numpy as np

    try:
        frozen = _frozen_bert(batch, seq, hidden, layers, heads, vocab)
    except Exception as e:  # pragma: no cover - env-dependent
        return {"error": f"tf/transformers unavailable: {e}"}

    from deeplearning4j_tpu.samediff import TrainingConfig
    from deeplearning4j_tpu.samediff.tf_import import TFGraphMapper
    from deeplearning4j_tpu.train.updaters import Adam

    gd = frozen.graph.as_graph_def()
    in_name = frozen.inputs[0].name.split(":")[0]
    out_name = frozen.outputs[0].name.split(":")[0]
    sd = TFGraphMapper.import_graph(gd, outputs=[out_name])
    converted = sd.convert_to_variables()

    hidden_var = sd.get_variable(out_name)                # [b, t, h]
    pooled = sd._op("reduce_mean", hidden_var, axis=[1])
    w = sd.var("cls_W", shape=(hidden, 2))
    logits = sd._op("matmul", pooled, w, name="logits")
    labels = sd.placeholder("labels", dtype="float32")
    loss = sd._op("softmax_cross_entropy", labels, logits)
    sd._op("reduce_mean", loss, name="loss")
    sd.set_loss_variables("loss")

    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (batch, seq)).astype(np.int32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, batch)]
    cfg = TrainingConfig(
        updater=Adam(1e-5),
        data_set_feature_mapping=[in_name],
        data_set_label_mapping=["labels"],
    )
    # warmup fit compiles the full train-step HLO
    sd.fit([(ids, y)] * 2, cfg, epochs=1)
    probe = max(converted, key=lambda n: sd._values[sd._names[n]].size)
    _host_fence(sd._values[sd._names[probe]])

    def block():
        start = time.perf_counter()
        sd.fit([(ids, y)] * bench_iters, cfg, epochs=1)
        _host_fence(sd._values[sd._names[probe]])
        return time.perf_counter() - start

    rate, spread = _median_rate(block, batch * seq * bench_iters)
    return {
        "tokens_per_sec": rate, "spread": spread,
        "batch": batch, "seq": seq,
        "step_ms": batch * seq / rate * 1e3,
        "trainable_imported_vars": len(converted),
        "model": f"TF-imported BERT-base (L={layers}, H={hidden}) + cls head",
        "mode": "training full-graph HLO (import -> convert_to_variables "
                "-> sd.fit, Adam)",
    }


def measure_input_pipeline(n_images: int = 384, raw: int = 256,
                           out: int = 224, workers: int = None) -> dict:
    """Host input-path throughput in its three modes (decode + augment +
    batch; SURVEY.md:124 'the ImageNet input path'), each median-of-3:
      * float32 host-augment — the reference-shaped path (full float math
        on host);
      * uint8 host-augment — geometric transforms as u8 views;
      * uint8 passthrough — zero per-pixel host math; augmentation runs
        on device (see resnet50_e2e_fit).
    Compare against the device step rate to decide input- vs
    compute-bound."""
    import shutil
    import tempfile

    import numpy as np

    from deeplearning4j_tpu.data.image_transform import (
        FlipImageTransform, PipelineImageTransform, RandomCropTransform,
    )
    from deeplearning4j_tpu.data.records import (
        ImageRecordReader, RecordReaderDataSetIterator, resolve_data_workers,
    )

    # the ACTUAL decode/augment pool size (explicit arg >
    # DL4J_TPU_DATA_WORKERS env > 1), reported as host_workers_available
    workers_used = resolve_data_workers(workers)

    tmp = tempfile.mkdtemp(prefix="bench_imgs_")
    try:
        rng = np.random.RandomState(0)
        header = f"P6 {raw} {raw} 255\n".encode()
        for cls in ("a", "b"):
            os.makedirs(os.path.join(tmp, cls), exist_ok=True)
        for i in range(n_images):
            body = rng.randint(0, 256, (raw, raw, 3), np.uint8).tobytes()
            with open(os.path.join(tmp, "ab"[i % 2], f"{i}.ppm"), "wb") as f:
                f.write(header + body)

        def run_mode(output_dtype, augment, size):
            aug = None
            if augment:
                aug = PipelineImageTransform(
                    (FlipImageTransform(mode=1), 0.5),
                    RandomCropTransform(height=size, width=size))
            reader = ImageRecordReader(size, size, 3, root=tmp,
                                       transform=aug,
                                       output_dtype=output_dtype,
                                       workers=workers_used)
            it = RecordReaderDataSetIterator(reader, batch_size=32,
                                             label_index=1, num_classes=2)

            def block():
                start = time.perf_counter()
                n = 0
                for ds in it:
                    n += ds.features.shape[0]
                assert n == n_images
                return time.perf_counter() - start

            block()  # warm page cache
            rate, spread = _median_rate(block, n_images)
            return {"images_per_sec": round(rate, 1), "spread": spread}

        return {
            "float32_host_augment": run_mode("float32", True, out),
            "uint8_host_augment": run_mode("uint8", True, out),
            "uint8_passthrough": run_mode("uint8", False, raw),
            "n_images": n_images, "raw_size": raw, "crop": out,
            "host_workers_available": workers_used,
            "host_cpu_count": os.cpu_count(),
            "augmentation": "flip(p=0.5) + random_crop (host modes); "
                            "device-side for passthrough",
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ResNet-50's hot conv shape table, derived from the zoo graph.
# Grouped by (spatial, kind);
# weight_gflops = per-image forward FLOPs of ALL convs the group represents
# (counts folded in). Sum = 7.712 GFLOP/img fwd conv — consistent with the
# canonical 3.86 GMAC figure for ResNet-50 at 224.
_RESNET_CONV_GROUPS = [
    # (name, kind, hw, ci, co, k, stride, weight_gflops)
    ("conv1_7x7s2", "accum", 224, 3, 64, 7, 2, 0.236),
    ("s1_3x3_64@56", "chain", 56, 64, 64, 3, 1, 0.694),
    ("s2_3x3_128@28", "chain", 28, 128, 128, 3, 1, 0.925),
    ("s3_3x3_256@14", "chain", 14, 256, 256, 3, 1, 1.387),
    ("s4_3x3_512@7", "chain", 7, 512, 512, 3, 1, 0.694),
    ("s1_1x1_64-256@56", "pair", 56, 64, 256, 1, 1, 0.643),
    ("s2_1x1_128-512@28", "pair", 28, 128, 512, 1, 1, 0.720),
    ("s3_1x1_256-1024@14", "pair", 14, 256, 1024, 1, 1, 1.131),
    ("s4_1x1_512-2048@7", "pair", 7, 512, 2048, 1, 1, 0.514),
    ("ds_1x1s2@56", "accum", 56, 256, 512, 1, 2, 0.514),
    ("ds_1x1s2@14", "accum", 14, 1024, 2048, 1, 2, 0.257),
]


def measure_calibration(n: int = 4096, chain: int = 100,
                        conv_batch: int = 64, tiny: bool = False) -> dict:
    """Measured-peak calibration + timer self-check + ResNet conv roofline.

    Matmul peak: a fori_loop of n*n bf16 matmuls timed at ``chain`` and
    ``2*chain`` iterations, rate from the two-point delta (median-of-3) —
    the honest MXU ceiling for matmul-shaped work. ``timer_disagreement`` compares
    block_until_ready against the host fence (>2x means block timing lies).

    Conv roofline (VERDICT r4 ask 1): each ResNet-50 hot-shape GROUP is
    timed as a chained jit program (stride-1 same-channel convs feed
    forward; expand/reduce 1x1s alternate in pairs; strided shapes use an
    input-perturbation accumulation chain), median-of-3 per shape with
    spread. ``conv_ceiling_tflops`` is the FLOPs-weighted harmonic mean —
    the throughput a model would see if it ran ONLY these convs
    back-to-back. The ResNet MFU gate divides by this ceiling, which by
    construction the full train step cannot exceed (it adds backward,
    BN/ReLU and optimizer work at no-better efficiency)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from deeplearning4j_tpu.bench.peak import chip_peak_flops

    if tiny:  # explicit CPU spelling: shrink everything, record that we did
        n, chain, conv_batch = 512, 4, 2

    # TWO-POINT ASYMPTOTIC FIT: every fenced dispatch has a fixed cost, so
    # a single-length measurement understates the hardware rate. Timing
    # the same program at N and 2N iterations and dividing the flop delta
    # by the time delta cancels the fixed cost exactly, whatever it is
    # (~0.6 ms on the local v5e, chip_smoke.py env_facts, PR 21). Both the
    # matmul peak and every conv shape use this estimator;
    # ``fixed_dispatch_ms`` reports the intercept.
    def asymptotic_rate(make_prog, flops_per_iter, n1=None, repeats=REPEATS,
                        tiny_cfg=tiny):
        """make_prog(n_iters) -> jitted fn(x)->y with ``example_input``;
        returns (rate_flops_per_s, spread_dict, fixed_ms).

        When ``n1`` is None, a pilot run sizes the base length so the
        N-vs-2N time DELTA is ~80 ms of pure compute — large against the
        few-ms run-to-run noise, so the pairwise quotients stay sane."""
        if n1 is None:
            n_p = max(8, int((2e8 if tiny_cfg else 5e11) / flops_per_iter))
            pp = make_prog(n_p)
            _host_fence(pp(pp.example_input))
            start = time.perf_counter()
            _host_fence(pp(pp.example_input))
            t_p = time.perf_counter() - start
            rate_p = flops_per_iter * n_p / t_p
            target_s = 0.01 if tiny_cfg else 0.08
            n1 = max(8, int(target_s * rate_p / flops_per_iter))
        min_delta_s = 0.002 if tiny_cfg else 0.04
        for attempt in range(3):
            p1, p2 = make_prog(n1), make_prog(2 * n1)
            t1s, t2s = [], []
            for p, ts in ((p1, t1s), (p2, t2s)):
                xin = p.example_input
                _host_fence(p(xin))  # compile + drain
                for _ in range(repeats):
                    start = time.perf_counter()
                    _host_fence(p(xin))
                    ts.append(time.perf_counter() - start)
            delta = statistics.median(t2s) - statistics.median(t1s)
            if delta >= min_delta_s or attempt == 2:
                break
            # delta lost in dispatch noise: double the base length so the
            # pairwise quotients are measuring compute, not jitter
            # (n1 is what t1s/t2s were measured at — only grow BEFORE a
            # remeasure, never after the last one)
            n1 *= 2
        d_flops = flops_per_iter * n1
        delta_med = max(statistics.median(t2s) - statistics.median(t1s),
                        1e-9)
        med = d_flops / delta_med  # value from the MEDIAN delta
        # spread from pairwise quotients, excluding pairs whose delta
        # collapsed into timing noise (< 40% of the median delta) — those
        # produce physically impossible rates, not information
        deltas = [t2 - t1 for t1, t2 in zip(sorted(t1s), sorted(t2s))]
        good = [d for d in deltas if d > 0.4 * delta_med]
        rates = [d_flops / d for d in (good or [delta_med])]
        fixed_ms = (statistics.median(t1s) - d_flops / med) * 1e3
        return med, {
            "min": round(min(rates) / 1e12, 2),
            "max": round(max(rates) / 1e12, 2), "n": repeats,
            # 0 = no pairwise delta survived the noise filter; the spread
            # then just echoes the median-delta rate (not a measured pair)
            "n_pairs_used": len(good),
            "n_iter_base": n1,
        }, round(fixed_ms, 1)

    x_mm = jnp.ones((n, n), jnp.bfloat16)

    def make_mm(iters):
        fn = jax.jit(lambda x: lax.fori_loop(
            0, iters, lambda i, x: (x @ x) * (1.0 / n), x))
        fn.example_input = x_mm
        return fn

    mm_flops_iter = 2.0 * n * n * n
    mm_rate, mm_spread, mm_fixed_ms = asymptotic_rate(
        make_mm, mm_flops_iter, chain)

    # block_until_ready comparison (single shot: it exists to prove the
    # disagreement, not to be a measurement)
    p = make_mm(chain)
    _host_fence(p(x_mm))  # warm: exclude trace+compile from the probe
    start = time.perf_counter()
    y = p(x_mm)
    jax.block_until_ready(y)
    block_tflops = mm_flops_iter * chain / (time.perf_counter() - start) / 1e12
    _host_fence(y)

    # ---- conv roofline on ResNet-50's own shapes -----------------------
    # Repetition runs ON DEVICE via lax.fori_loop (one dispatch, one
    # fence), so a host-looped chain's per-dispatch cost (~0.2 ms queued
    # on the local v5e) cannot be mistaken for conv time. Strided/channel-changing
    # shapes pair the conv with its conv_transpose (the dgrad shape from
    # training) to keep the loop carry static — the pair rate is what a
    # train step actually sees for those layers.
    def norm(key, shape):
        return jax.random.normal(jax.random.PRNGKey(key), shape,
                                 jnp.bfloat16) * 0.05

    dn = ("NCHW", "OIHW", "NCHW")

    def conv(x, w, s):
        return lax.conv_general_dilated(
            x, w, window_strides=(s, s), padding="SAME",
            dimension_numbers=lax.conv_dimension_numbers(x.shape, w.shape,
                                                         dn))

    def convT(y, w, s):
        # transposed conv (dgrad shape): kernel [ci, co, k, k] flipped use
        return lax.conv_transpose(
            y, w, strides=(s, s), padding="SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    per_shape = {}
    total_w = 0.0
    total_time_per_gf = 0.0  # sum(weight_i / rate_i)
    for name, kind, hw, ci, co, k, s, weight in _RESNET_CONV_GROUPS:
        b = conv_batch
        oh = -(-hw // s)
        if kind == "chain":
            w = norm(1, (ci, ci, k, k))
            f_iter = 2.0 * b * oh * oh * k * k * ci * ci

            def body(i, xx, w=w, s=s):
                return conv(xx, w, s) * 0.05
        elif kind == "pair":
            w1 = norm(1, (co, ci, 1, 1))
            w2 = norm(2, (ci, co, 1, 1))
            f_iter = 2.0 * b * hw * hw * ci * co * 2

            def body(i, xx, w1=w1, w2=w2):
                return conv(conv(xx, w1, 1) * 0.05, w2, 1) * 0.05
        else:  # strided/channel-changing: fwd conv + its dgrad transpose
            w = norm(1, (co, ci, k, k))
            wT = norm(2, (ci, co, k, k))  # transpose kernel: I = co
            f_iter = 2.0 * b * oh * oh * k * k * ci * co * 2

            def body(i, xx, w=w, wT=wT, s=s):
                yy = conv(xx, w, s) * 0.05          # [b, co, oh, ow]
                return convT(yy, wT, s) * 0.05      # back to [b, ci, hw, hw]
        xin = norm(3, (b, ci, hw, hw))

        def make_prog(iters, body=body, xin=xin):
            fn = jax.jit(lambda xx: lax.fori_loop(0, iters, body, xx))
            fn.example_input = xin
            return fn

        rate, spread, fixed_ms = asymptotic_rate(make_prog, f_iter)
        tfl = rate / 1e12
        per_shape[name] = {
            "tflops": round(tfl, 2),
            "spread_tflops": spread,
            "weight_gflops_per_img": weight,
            "fixed_dispatch_ms": fixed_ms,
            "shape": f"b{b} {ci}->{co} k{k} s{s} @{hw}" + (
                " (+conv_transpose dgrad pair)" if kind == "accum" else ""),
        }
        total_w += weight
        total_time_per_gf += weight / max(tfl, 1e-9)

    conv_ceiling = total_w / total_time_per_gf  # FLOPs-weighted harmonic

    peak = chip_peak_flops(jax.devices()[0], "bfloat16")
    return {
        "measured_peak_tflops": round(mm_rate / 1e12, 2),
        "matmul_spread_tflops": mm_spread,
        "fixed_dispatch_ms": mm_fixed_ms,
        "estimator": "two-point asymptotic fit (N vs 2N fori_loop iters); "
                     "cancels the fixed cost of a fenced dispatch",
        "conv_ceiling_tflops": round(conv_ceiling, 2),
        "conv_per_shape": per_shape,
        "conv_batch": conv_batch,
        "conv_fwd_gflops_per_img": round(total_w, 3),
        "block_timed_tflops": round(block_tflops, 2),
        "timer_disagreement": round(block_tflops / (mm_rate / 1e12), 2),
        "spec_peak_tflops": round(peak / 1e12, 1) if peak else None,
        "matmul_n": n, "chain_base": chain,
        "tiny_cpu_config": tiny,
    }


def _timed_calls_ms(fn, args, n_iters, repeats: int = REPEATS):
    """Median ms per call of ``fn(*args)`` over ``repeats`` fenced blocks
    of ``n_iters`` queued calls each (single amortized fence per block).
    Returns (median_ms, spread_ms_dict)."""
    out = fn(*args)
    _fence_tree(out)

    def block():
        start = time.perf_counter()
        o = None
        for _ in range(n_iters):
            o = fn(*args)
        _fence_tree(o)
        return time.perf_counter() - start

    rate, spread = _median_rate(block, n_iters)  # calls/sec
    return 1e3 / rate, {"min_ms": round(1e3 / spread["max"], 2),
                        "max_ms": round(1e3 / spread["min"], 2),
                        "n": spread["n"]}


def measure_flash_attention_8k(b: int = 1, h: int = 8, t: int = 8192,
                               d: int = 64, iters: int = 8) -> dict:
    """Long-context attention rows (SURVEY §5.7): compiled Pallas flash
    kernel vs the XLA dense reference, forward and backward, median-of-3
    with spread. Also times the backward at 16k/32k where the memory
    story dominates (dense materializes t^2: ~2x slower at 16k and fails
    to compile at 32k; flash is O(t*d))."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.flash_attention import (
        flash_attention, mha_attention_reference)

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (b, h, t, d),
                                 jnp.bfloat16) for i in range(3))

    def timed(fn, args, n_iters=iters):
        return _timed_calls_ms(fn, args, n_iters)

    def bwd(fn):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(jnp.square(
                fn(q, k, v).astype(jnp.float32))), argnums=(0, 1, 2)))

    flash = jax.jit(lambda q, k, v: flash_attention(q, k, v,
                                                    interpret=False))
    dense = jax.jit(mha_attention_reference)
    flash_c = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False))
    dense_c = jax.jit(lambda q, k, v: mha_attention_reference(
        q, k, v, causal=True))

    rows = {"seq": t, "batch": b, "heads": h, "head_dim": d}
    f_ms, f_sp = timed(flash, (q, k, v))
    d_ms, d_sp = timed(dense, (q, k, v))
    fc_ms, fc_sp = timed(flash_c, (q, k, v))
    dc_ms, dc_sp = timed(dense_c, (q, k, v))
    fb_ms, fb_sp = timed(bwd(lambda q, k, v: flash_attention(
        q, k, v, interpret=False)), (q, k, v), max(iters // 2, 3))
    db_ms, db_sp = timed(bwd(mha_attention_reference), (q, k, v),
                         max(iters // 2, 3))
    fcb_ms, fcb_sp = timed(bwd(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False)), (q, k, v),
        max(iters // 2, 3))
    dcb_ms, dcb_sp = timed(bwd(lambda q, k, v: mha_attention_reference(
        q, k, v, causal=True)), (q, k, v), max(iters // 2, 3))
    rows.update({
        "flash_ms": round(f_ms, 2), "flash_spread": f_sp,
        "xla_dense_ms": round(d_ms, 2), "xla_spread": d_sp,
        "speedup_vs_dense": round(d_ms / f_ms, 2),
        "causal_flash_ms": round(fc_ms, 2), "causal_flash_spread": fc_sp,
        "causal_xla_ms": round(dc_ms, 2), "causal_xla_spread": dc_sp,
        "causal_speedup": round(dc_ms / fc_ms, 2),
        "backward_flash_ms": round(fb_ms, 2), "backward_flash_spread": fb_sp,
        "backward_xla_ms": round(db_ms, 2), "backward_xla_spread": db_sp,
        "backward_speedup": round(db_ms / fb_ms, 2),
        "causal_backward_flash_ms": round(fcb_ms, 2),
        "causal_backward_xla_ms": round(dcb_ms, 2),
        "causal_backward_speedup": round(dcb_ms / fcb_ms, 2),
        "backward_impl": "Pallas dq+dkv kernels (bf16 operands, f32 "
                         "accumulation, causal block skip)",
    })

    # long-context backward scaling: flash stays O(t*d); dense is O(t^2)
    if t >= 8192:
        long_rows = {}
        for tl in (16384, 32768):
            ql, kl, vl = (jax.random.normal(jax.random.PRNGKey(i),
                                            (1, h, tl, d), jnp.bfloat16)
                          for i in range(3))
            fl_ms, fl_sp = timed(bwd(lambda q, k, v: flash_attention(
                q, k, v, causal=True, interpret=False)), (ql, kl, vl), 2)
            row = {"flash_causal_bwd_ms": round(fl_ms, 1),
                   "flash_spread": fl_sp}
            try:
                dl_ms, _ = timed(bwd(lambda q, k, v: mha_attention_reference(
                    q, k, v, causal=True)), (ql, kl, vl), 2)
                row["dense_causal_bwd_ms"] = round(dl_ms, 1)
                row["speedup"] = round(dl_ms / fl_ms, 2)
            except Exception as e:
                row["dense_causal_bwd_ms"] = None
                row["dense_error"] = str(e)[:120]
            long_rows[f"t{tl}"] = row
        rows["long_context_backward"] = long_rows
    return rows


def measure_moe_dispatch(tokens: int = 8192, d: int = 768, experts: int = 8,
                         top_k: int = 2, hidden: int = 1536,
                         iters: int = 10) -> dict:
    """MoE dispatch overhead (VERDICT r4 ask 10; ISSUE 3 + 18): one
    MixtureOfExperts train step (fwd+bwd) vs a dense 2-layer FFN doing the
    SAME per-token matmul FLOPs (dense hidden = top_k * expert hidden).
    Measures ALL THREE dispatch modes — "sort" (gather/scatter, the
    default), "einsum" (legacy dense one-hot) and "grouped" (sorted
    grouped expert matmul, ops.grouped_matmul) — so the
    ``dispatch_overhead_ratio`` trajectory records the dispatch wins; the
    headline ratio follows the default mode. Gates:
    ``grouped_no_regression_vs_sort`` (grouped must stay within the
    headroom of sort — holds on any platform, this is the CI smoke) and
    the ≤ 1.5 ``grouped_dispatch_overhead_ratio`` target, which is
    CHIP-ONLY (on a CPU host the XLA-reference grouped spelling pays
    gather/scatter without an MXU to amortize it; recorded, not
    asserted)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers import MixtureOfExpertsLayer
    from deeplearning4j_tpu.nn.layers.base import LayerContext

    params = None
    mode_ms = {}
    mode_sp = {}
    for mode in ("sort", "einsum", "grouped"):
        lay = MixtureOfExpertsLayer(
            n_in=d, n_out=d, num_experts=experts, hidden=hidden, top_k=top_k,
            capacity_factor=1.25, dispatch_mode=mode)
        if params is None:  # identical params across modes (same pytree)
            params = lay.init(jax.random.PRNGKey(0), jnp.bfloat16)
        state = lay.init_state(jnp.bfloat16)
        x = jax.random.normal(jax.random.PRNGKey(1), (tokens, d),
                              jnp.bfloat16)

        def moe_loss(params, x, _lay=lay, _state=state):
            y, _ = _lay.apply(params, _state, x, LayerContext())
            return jnp.sum(jnp.square(y.astype(jnp.float32)))

        moe_g = jax.jit(jax.grad(moe_loss))
        mode_ms[mode], mode_sp[mode] = _timed_calls_ms(
            moe_g, (params, x), iters)

    dh = top_k * hidden
    w1 = jax.random.normal(jax.random.PRNGKey(2), (d, dh), jnp.bfloat16) * .02
    w2 = jax.random.normal(jax.random.PRNGKey(3), (dh, d), jnp.bfloat16) * .02

    def dense_loss(ws, x):
        w1, w2 = ws
        y = jax.nn.relu(x @ w1) @ w2
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    dense_g = jax.jit(jax.grad(dense_loss))

    moe_ms = mode_ms["sort"]  # the default dispatch_mode is the headline
    dense_ms, dense_sp = _timed_calls_ms(dense_g, ((w1, w2), x), iters)
    grouped_ratio = mode_ms["grouped"] / dense_ms
    # sort already does the heavy lifting (static [E, C] buffers); grouped
    # swaps the buffer matmuls for frontier-skipping grouped kernels. On
    # CPU both lower to the same XLA gather/einsum shapes, so "no
    # regression" with modest headroom is the honest portable gate; the
    # grouped WIN (skipped tiles) only materializes on the chip.
    no_reg_headroom = 1.3
    return {
        "tokens": tokens, "d_model": d, "experts": experts, "top_k": top_k,
        "expert_hidden": hidden,
        "moe_grad_step_ms": round(moe_ms, 2),
        "moe_spread_ms": mode_sp["sort"],
        "moe_sort_grad_step_ms": round(mode_ms["sort"], 2),
        "moe_einsum_grad_step_ms": round(mode_ms["einsum"], 2),
        "moe_einsum_spread_ms": mode_sp["einsum"],
        "moe_grouped_grad_step_ms": round(mode_ms["grouped"], 2),
        "moe_grouped_spread_ms": mode_sp["grouped"],
        "dense_equal_flops_grad_step_ms": round(dense_ms, 2),
        "dense_spread_ms": dense_sp,
        "dispatch_overhead_ratio": round(moe_ms / dense_ms, 2),
        "einsum_dispatch_overhead_ratio": round(
            mode_ms["einsum"] / dense_ms, 2),
        "grouped_dispatch_overhead_ratio": round(grouped_ratio, 2),
        "sort_vs_einsum_speedup": round(mode_ms["einsum"] / moe_ms, 2),
        "grouped_vs_sort_speedup": round(moe_ms / mode_ms["grouped"], 2),
        "grouped_no_regression_vs_sort": {
            "max_ratio": no_reg_headroom,
            "ratio": round(mode_ms["grouped"] / moe_ms, 2),
            "ok": bool(mode_ms["grouped"] <= no_reg_headroom * moe_ms)},
        "grouped_overhead_chip_target": {
            "max": 1.5, "measured": round(grouped_ratio, 2),
            "chip_only": True},
        "note": "dense hidden = top_k*expert_hidden so per-token matmul "
                "FLOPs match; ratio > 1 is routing + dispatch/combine cost; "
                "headline ratio uses dispatch_mode='sort' (the default); "
                "grouped_overhead_chip_target is asserted on TPU only",
    }


def measure_rewrite_passes(batch: int = 128, height: int = 224,
                           width: int = 224, classes: int = 1000,
                           warmup_iters: int = 3, bench_iters: int = 10,
                           infer_iters: int = 20,
                           compute_dtype: str = "bfloat16") -> dict:
    """Graph-rewrite pass deltas (ISSUE 5): ResNet-50 train step with the
    training-safe rewrites on vs off (space-to-depth stem + BN affine
    precompute, isolating the stem pass for ``stem_rewrite_speedup``) and
    inference forward with conv+BN folding on vs off
    (``bn_fold_infer_speedup``). Rewrites are numerically equivalent
    (tools/check_rewrite_equivalence.py), so any delta is pure step time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.model.zoo import ResNet50
    from deeplearning4j_tpu.nn.rewrite import (
        SpaceToDepthStemPass, rewrite_model,
    )
    from deeplearning4j_tpu.train.graph_solver import GraphSolver

    cd = None if compute_dtype in (None, "float32") else compute_dtype
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 3, height, width), jnp.float32)
    y_np = np.zeros((batch, classes), np.float32)
    y_np[np.arange(batch), rng.randint(0, classes, batch)] = 1.0
    y = jnp.asarray(y_np)

    def build():
        return ResNet50(seed=42, num_classes=classes, height=height,
                        width=width, compute_dtype=cd).init()

    def step_ms(solver) -> float:
        for _ in range(warmup_iters):
            solver.fit_batch((x,), (y,))
        _host_fence(solver.model.params)

        def block():
            start = time.perf_counter()
            for _ in range(bench_iters):
                solver.fit_batch((x,), (y,))
            _host_fence(solver.model.params)
            return time.perf_counter() - start

        rate, _ = _median_rate(block, bench_iters)
        return 1e3 / rate

    baseline = build()
    off_ms = step_ms(GraphSolver(baseline))
    stem_ms = step_ms(GraphSolver(build(),
                                  optimize=[SpaceToDepthStemPass()]))
    on_solver = GraphSolver(build(), optimize="training")
    on_ms = step_ms(on_solver)

    def infer_ms(model) -> float:
        fwd = jax.jit(lambda p, s, xx: model.forward_pure(
            p, s, xx, train=False, rng=None)[0])
        _host_fence(fwd(model.params, model.state, (x,)))

        def block():
            start = time.perf_counter()
            o = None
            for _ in range(infer_iters):
                o = fwd(model.params, model.state, (x,))
            _host_fence(o)
            return time.perf_counter() - start

        rate, _ = _median_rate(block, infer_iters)
        return 1e3 / rate

    unfolded_ms = infer_ms(baseline)
    folded, applied = rewrite_model(baseline, "inference")
    folded_ms = infer_ms(folded)
    return {
        "batch": batch, "compute_dtype": compute_dtype,
        "resnet50_step_ms_rewrites_off": round(off_ms, 2),
        "resnet50_step_ms_stem_only": round(stem_ms, 2),
        "resnet50_step_ms_rewrites_on": round(on_ms, 2),
        "stem_rewrite_speedup": round(off_ms / stem_ms, 3),
        "train_rewrites_speedup": round(off_ms / on_ms, 3),
        "train_passes_applied": on_solver.applied_rewrites,
        "resnet50_infer_ms_unfolded": round(unfolded_ms, 2),
        "resnet50_infer_ms_folded": round(folded_ms, 2),
        "bn_fold_infer_speedup": round(unfolded_ms / folded_ms, 3),
        "infer_passes_applied": applied,
        "note": "rewrites are numerically equivalent; speedups are pure "
                "step-time deltas (stem MXU occupancy + BN HBM traffic)",
    }


def measure_tracing_overhead(n_requests: int = 150, warmup: int = 30,
                             repeats: int = 6) -> dict:
    """ISSUE 6 acceptance: per-request serving latency with distributed
    tracing ON (default sampling, ~6 spans/request across
    client->server->engine) vs OFF, over real loopback HTTP.

    Methodology: tracing on/off is a deployment choice, so each mode gets
    a FRESH server+client pair (a shared toggled server carries state
    across modes); pairs run back-to-back so thermal/scheduler drift hits
    both, and the reported overhead is the median of the paired relative
    deltas. Server-side span cost is also reported directly from the
    request-latency histogram — the span work largely hides inside the
    request's pipeline slack, which is why the e2e budget (<3%) holds."""
    import numpy as np

    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.obs import MetricsRegistry
    from deeplearning4j_tpu.obs.tracing import TraceStore, Tracer
    from deeplearning4j_tpu.remote import JsonModelServer
    from deeplearning4j_tpu.remote.server import JsonRemoteInference

    conf = (NeuralNetConfiguration.builder().seed(5).list()
            .layer(DenseLayer(n_in=16, n_out=32))
            .layer(OutputLayer(n_in=32, n_out=8))
            .build())
    model = MultiLayerNetwork(conf).init()
    x = np.random.RandomState(0).randn(1, 16).astype(np.float32).tolist()

    from deeplearning4j_tpu.obs.tracing import DEFAULT_SAMPLE_RATE

    def trimmed_mean(lat):
        # drop the top decile: robust to scheduler spikes but, unlike the
        # median, still charges sampled requests their span cost at
        # fractional sampling
        lat = sorted(lat)
        keep = lat[:max(1, int(len(lat) * 0.9))]
        return sum(keep) / len(keep)

    def paired_run(sample_rate: float):
        """One server+client; requests ALTERNATE tracing off/on so the
        host's multi-percent latency drift (this is a shared 1-core box)
        hits both populations identically — the only systematic
        difference between the two trimmed means is the tracing cost."""
        registry = MetricsRegistry()
        tracer = Tracer(TraceStore(max_traces=64), enabled=True,
                        sample_rate=sample_rate)
        srv = JsonModelServer(model, port=0, workers=1, batch_limit=8,
                              registry=registry, tracer=tracer).start()
        cli = JsonRemoteInference(f"http://127.0.0.1:{srv.port}/v1/serving",
                                  registry=registry, tracer=tracer)
        lat = {False: [], True: []}
        try:
            for _ in range(warmup):
                cli.predict(x)
            for i in range(2 * n_requests * repeats):
                enabled = bool(i % 2)
                tracer.enabled = enabled
                t0 = time.perf_counter()
                cli.predict(x)
                lat[enabled].append(time.perf_counter() - t0)
        finally:
            srv.stop()
        return trimmed_mean(lat[False]), trimmed_mean(lat[True])

    off_s, on_s = paired_run(DEFAULT_SAMPLE_RATE)
    off2_s, full_s = paired_run(1.0)
    overhead_pct = (on_s - off_s) / off_s * 100.0
    full_pct = (full_s - off2_s) / off2_s * 100.0
    return {
        "requests_per_mode": n_requests * repeats,
        "default_sample_rate": DEFAULT_SAMPLE_RATE,
        "latency_ms_tracing_off": round(off_s * 1e3, 4),
        "latency_ms_tracing_on": round(on_s * 1e3, 4),
        "latency_ms_tracing_full": round(full_s * 1e3, 4),
        "tracing_overhead_pct": round(overhead_pct, 2),
        "tracing_overhead_pct_full_sampling": round(full_pct, 2),
        "budget_pct": 3.0,
        "within_budget": overhead_pct < 3.0,
        "spans_per_request": 6,
        "note": "per-request-interleaved paired trimmed means on one "
                "server; ON = default head sampling (unsampled requests "
                "take the byte-identical off path, sampled ones carry "
                "the full client/server/engine span tree); full-sampling "
                "overhead alongside. This host is 1 CPU core — span cost "
                "is fully serial here; parallel slack absorbs most of it "
                "on real serving hosts",
    }


def measure_step_profile(batch: int = 128, n_images: int = 512,
                         raw: int = 256, out: int = 224,
                         bench_steps: int = 12, synth_steps: int = 8,
                         sync_every: int = 4) -> dict:
    """StepProfiler on the ResNet-50 FROM-FILES fit (ISSUE 6 acceptance):
    the per-phase breakdown (data_wait / h2d / compute / host) must
    EXPLAIN the e2e-vs-synthetic throughput ratio — when the pipeline is
    transfer-bound, the non-compute share is where the missing rate
    went."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.data.image_transform import (
        batch_random_crop, batch_random_flip,
    )
    from deeplearning4j_tpu.data.iterators import (
        AsyncDataSetIterator, MappedDataSetIterator, device_put_dataset,
    )
    from deeplearning4j_tpu.data.records import (
        ImageRecordReader, RecordReaderDataSetIterator,
    )
    from deeplearning4j_tpu.model.zoo import ResNet50
    from deeplearning4j_tpu.obs import MetricsRegistry, StepProfiler
    from deeplearning4j_tpu.train.graph_solver import GraphSolver

    tmp = tempfile.mkdtemp(prefix="bench_prof_")
    try:
        rng = np.random.RandomState(0)
        header = f"P6 {raw} {raw} 255\n".encode()
        n_classes = 8
        for c in range(n_classes):
            os.makedirs(os.path.join(tmp, f"c{c}"), exist_ok=True)
        for i in range(n_images):
            body = rng.randint(0, 256, (raw, raw, 3), np.uint8).tobytes()
            with open(os.path.join(tmp, f"c{i % n_classes}", f"{i}.ppm"),
                      "wb") as f:
                f.write(header + body)

        model = ResNet50(seed=42, num_classes=n_classes,
                         compute_dtype="bfloat16").init()
        key = jax.random.PRNGKey(0)

        def prep(features):
            x = jnp.transpose(jnp.asarray(features), (0, 3, 1, 2))
            x = x.astype(jnp.float32) * (1.0 / 255.0)
            x = batch_random_crop(x, key, out, out)
            return batch_random_flip(x, key)

        prep_j = jax.jit(prep)

        # ---- synthetic reference rate: same step, data already staged --
        solver = GraphSolver(model)
        x_syn = jnp.asarray(rng.rand(batch, 3, out, out), model.dtype)
        y_syn = jnp.asarray(np.eye(n_classes, dtype=np.float32)[
            rng.randint(0, n_classes, batch)])
        solver.fit_batch((x_syn,), (y_syn,))  # compile
        _host_fence(model.params)
        t0 = time.perf_counter()
        for _ in range(synth_steps):
            solver.fit_batch((x_syn,), (y_syn,))
        _host_fence(model.params)
        synth_rate = batch * synth_steps / (time.perf_counter() - t0)

        # ---- profiled from-files fit ----------------------------------
        registry = MetricsRegistry()
        prof = StepProfiler(sync_every=sync_every, registry=registry)
        psolver = GraphSolver(model, profiler=prof)

        def make_iter():
            reader = ImageRecordReader(raw, raw, 3, root=tmp,
                                       output_dtype="uint8")
            base = RecordReaderDataSetIterator(
                reader, batch_size=batch, label_index=1,
                num_classes=n_classes)
            return prof.wrap_iterator(MappedDataSetIterator(
                AsyncDataSetIterator(base, device_put_fn=device_put_dataset),
                feature_fn=prep_j))

        # warmup pass: compile + page cache, like resnet50_e2e_fit
        for ds in make_iter():
            if ds.features.shape[0] == batch:
                psolver.fit_batch((ds.features,), (ds.labels,))
                break
        _host_fence(model.params)
        prof_steps0 = prof.steps

        steps = 0
        t0 = time.perf_counter()
        while steps < bench_steps:
            for ds in make_iter():
                if ds.features.shape[0] != batch:
                    continue
                psolver.fit_batch((ds.features,), (ds.labels,))
                steps += 1
                if steps >= bench_steps:
                    break
        _host_fence(model.params)
        files_rate = batch * bench_steps / (time.perf_counter() - t0)

        s = prof.stats()
        ratio = files_rate / synth_rate
        compute_share = s["share"]["compute"]
        return {
            "batch": batch, "bench_steps": steps,
            "profiled_steps": prof.steps - prof_steps0,
            "sampled_steps": s["sampled_steps"],
            "sync_every": sync_every,
            "synthetic_samples_per_sec": round(synth_rate, 2),
            "files_samples_per_sec": round(files_rate, 2),
            "e2e_vs_synthetic": round(ratio, 4),
            "phase_share": s["share"],
            "phase_per_step_ms": s["per_step_ms"],
            "input_bound_share": s["input_bound_share"],
            "step_time_ms_est": s["step_time_ms_est"],
            # the breakdown must EXPLAIN the ratio: compute's share of the
            # from-files step ~= the throughput the pipeline retains
            "compute_share": compute_share,
            "breakdown_explains_ratio": round(
                abs(compute_share - ratio), 4),
            "note": "breakdown_explains_ratio = |compute_share - "
                    "e2e_vs_synthetic|; small means the data_wait+h2d "
                    "share accounts for the e2e gap (ISSUE 6 acceptance)",
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def measure_input_pipeline_overlap(n_images: int = 256, raw: int = 128,
                                   batch: int = 32,
                                   compute_iters: int = 6) -> dict:
    """Double-buffer win row (ISSUE 7 acceptance): same ppm files, same
    jitted step, two transfer schedules —

      * overlap OFF: the consumer decodes a batch, ``device_put``s it at
        DEQUEUE time, then dispatches the step — decode and H2D serialize
        with compute;
      * overlap ON: :class:`AsyncDataSetIterator` ``device_put``s at
        ENQUEUE time on the prefetch thread through a 2-deep device
        buffer ring, and the step donates its input buffer — decode +
        H2D for batch N+1 hide behind compute for batch N.

    ``overlap_speedup`` is the ratio: at best the whole decode+transfer
    wall, bounded by the H2D link (see resnet50_e2e_fit)."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.data.iterators import (
        AsyncDataSetIterator, device_put_dataset,
    )
    from deeplearning4j_tpu.data.records import (
        ImageRecordReader, RecordReaderDataSetIterator,
    )

    tmp = tempfile.mkdtemp(prefix="bench_ovl_")
    try:
        rng = np.random.RandomState(0)
        header = f"P6 {raw} {raw} 255\n".encode()
        for cls in ("a", "b"):
            os.makedirs(os.path.join(tmp, cls), exist_ok=True)
        for i in range(n_images):
            body = rng.randint(0, 256, (raw, raw, 3), np.uint8).tobytes()
            with open(os.path.join(tmp, "ab"[i % 2], f"{i}.ppm"), "wb") as f:
                f.write(header + body)

        # compute stand-in sized by compute_iters chained matmuls; the
        # accumulator chains every step so ONE final fence covers the block
        w = jnp.asarray(rng.rand(3 * raw, 3 * raw), jnp.float32)

        def step_fn(x, w, acc):
            h = x.astype(jnp.float32).reshape(x.shape[0], raw, 3 * raw)
            h = h * (1.0 / 255.0)
            for _ in range(compute_iters):
                h = jnp.tanh(h @ w)
            return acc + jnp.sum(h)

        step = jax.jit(step_fn, donate_argnums=(0,))

        def make_base():
            reader = ImageRecordReader(raw, raw, 3, root=tmp,
                                       output_dtype="uint8")
            return RecordReaderDataSetIterator(
                reader, batch_size=batch, label_index=1, num_classes=2)

        def block_off():
            acc = jnp.zeros(())
            start = time.perf_counter()
            for ds in make_base():
                if ds.features.shape[0] != batch:
                    continue
                x = jax.device_put(ds.features)  # H2D at dequeue
                acc = step(x, w, acc)
            _host_fence(acc)
            return time.perf_counter() - start

        def block_on():
            acc = jnp.zeros(())
            it = AsyncDataSetIterator(make_base(), queue_size=4,
                                      device_put_fn=device_put_dataset,
                                      device_buffers=2)
            start = time.perf_counter()
            try:
                while it.has_next():
                    ds = it.next()
                    if ds.features.shape[0] != batch:
                        continue
                    acc = step(ds.features, w, acc)
                _host_fence(acc)
                return time.perf_counter() - start
            finally:
                it.close()

        n_batches = n_images // batch
        block_off(); block_on()  # compile + page cache
        off_rate, off_spread = _median_rate(block_off, n_batches * batch)
        on_rate, on_spread = _median_rate(block_on, n_batches * batch)
        return {
            "overlap_off_images_per_sec": round(off_rate, 1),
            "overlap_off_spread": off_spread,
            "overlap_on_images_per_sec": round(on_rate, 1),
            "overlap_on_spread": on_spread,
            "overlap_speedup": round(on_rate / off_rate, 3),
            "n_images": n_images, "raw_size": raw, "batch": batch,
            "compute_iters": compute_iters,
            "note": "ON = device_put at enqueue (prefetch thread, 2-deep "
                    "device ring) + donated input buffers; OFF = "
                    "device_put at dequeue on the consumer. On a 1-core "
                    "host decode and compute contend for the CPU, so the "
                    "measured win underestimates a real multi-core TPU "
                    "host's",
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def measure_zero1_updater_headroom(nin: int = 256, hidden: int = 1024,
                                   nout: int = 256, batch_per_shard: int = 8,
                                   warmup_steps: int = 2, bench_steps: int = 6,
                                   force_devices: int = 0) -> dict:
    """ZeRO-1 updater-headroom row (ISSUE 8 acceptance): per-chip
    optimizer-state bytes with the weight update sharded 1/N over the
    data axis vs fully replicated, the max-fit model multiplier that
    headroom buys (params+opt budget: ``(P+O)/(P+O/N)``), fenced
    step-time for both layouts at the full DP width, and the measured
    compression ratio of both encoded gradient-exchange strategies
    (adaptive-threshold and top-k). ``force_devices`` forces N virtual
    host devices for the CPU spelling (the flag must land before backend
    init — the measurement child has not touched jax yet)."""
    if force_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={force_devices}"
            ).strip()

    import numpy as np

    from deeplearning4j_tpu.nn import (
        Activation, InputType, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.parallel import (
        DistributedTrainer, ThresholdCompressedSync, TopKCompressedSync,
        make_mesh)
    from deeplearning4j_tpu.train import Adam

    def build():
        conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
                .list()
                .layer(DenseLayer(n_out=hidden, activation=Activation.TANH))
                .layer(OutputLayer(n_out=nout, loss=LossFunction.MCXENT))
                .set_input_type(InputType.feed_forward(nin)).build())
        return MultiLayerNetwork(conf).init()

    mesh = make_mesh()
    n = int(mesh.shape["data"])
    batch = batch_per_shard * n
    rng = np.random.RandomState(0)
    x = rng.randn(batch, nin).astype(np.float32)
    y = np.eye(nout, dtype=np.float32)[rng.randint(0, nout, batch)]

    def timed_steps(trainer, k: int) -> float:
        _host_fence(trainer.params)
        start = time.perf_counter()
        for _ in range(k):
            trainer.fit_batch(x, y)
        _host_fence(trainer.params)
        return (time.perf_counter() - start) / k

    t_rep = DistributedTrainer(build(), mesh=mesh)
    t_z = DistributedTrainer(build(), mesh=mesh, zero1=True)
    timed_steps(t_rep, warmup_steps)
    timed_steps(t_z, warmup_steps)
    step_rep = timed_steps(t_rep, bench_steps)
    step_z = timed_steps(t_z, bench_steps)

    rep_bytes = t_rep.updater_state_bytes()
    z_bytes = t_z.updater_state_bytes()
    params_bytes = sum(
        int(np.prod(np.shape(p), dtype=np.int64)) * np.dtype(p.dtype).itemsize
        for lp in t_rep.model.params.values() for p in lp.values())
    opt_global = t_z.updater_state_bytes(per_replica=False)
    # per-chip params+opt budget: how much bigger a model fits once the
    # updater term shards (ZeRO-1's headline number; Adam: O == 2P)
    max_fit = (params_bytes + opt_global) / (params_bytes + z_bytes)

    def comp_ratio(strategy):
        t = DistributedTrainer(build(), mesh=mesh, strategy=strategy,
                               zero1=True, metrics_every=0)
        for _ in range(4):
            t.fit_batch(x, y)
        stats = t.compression_stats() or {}
        r = stats.get("compression_ratio")
        return round(r, 2) if r else None

    return {
        "n_devices": n,
        "batch": batch,
        "updater_state_bytes_replicated": int(rep_bytes),
        "updater_state_bytes_zero1_per_chip": int(z_bytes),
        "updater_shard_ratio": round(rep_bytes / max(z_bytes, 1), 2),
        "params_bytes": int(params_bytes),
        "max_fit_param_multiplier": round(max_fit, 3),
        "step_ms_replicated": round(step_rep * 1e3, 3),
        "step_ms_zero1": round(step_z * 1e3, 3),
        "zero1_step_overhead": round(step_z / max(step_rep, 1e-9), 3),
        "threshold_compression_ratio": comp_ratio(
            ThresholdCompressedSync(threshold=1e-3, target_density=0.01)),
        "topk_compression_ratio": comp_ratio(
            TopKCompressedSync(density=0.01)),
    }


def measure_large_batch_scaling(nin: int = 32, hidden: int = 64,
                                nout: int = 8, base_batch: int = 64,
                                steps: int = 40, bench_steps: int = 6,
                                force_devices: int = 0) -> dict:
    """Pod-scale large-batch row (ISSUE 14 acceptance): the trajectory-
    quality gate at up to 8x the baseline global batch — LAMB + linear
    warmup + distributed batch norm must land within tolerance of the
    small-batch Adam baseline's final loss on the bench task, with
    per-batch-size final loss + fenced step-time recorded — plus the
    bucketed-exchange no-regression gate: ``BucketedAllReduceSync``
    step-time no worse than the unbucketed all-reduce at full DP width
    AND the exact same trajectory (the overlap win needs a real DCN; the
    CPU gate is no-regression + exactness), with the bucket count/volume
    from ``compression_stats()`` in the row."""
    if force_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={force_devices}"
            ).strip()

    import numpy as np

    from deeplearning4j_tpu.nn import (
        Activation, InputType, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import (
        BatchNormalizationLayer, DenseLayer, OutputLayer)
    from deeplearning4j_tpu.parallel import (
        BucketedAllReduceSync, DistributedTrainer, make_mesh)
    from deeplearning4j_tpu.train import Adam, Lamb, WarmupSchedule

    mesh = make_mesh()
    n = int(mesh.shape["data"])

    def build(updater):
        conf = (NeuralNetConfiguration.builder().seed(7).updater(updater)
                .list()
                .layer(DenseLayer(n_out=hidden, activation=Activation.TANH))
                .layer(BatchNormalizationLayer())
                .layer(OutputLayer(n_out=nout, loss=LossFunction.MCXENT))
                .set_input_type(InputType.feed_forward(nin)).build())
        return MultiLayerNetwork(conf).init()

    # fixed learnable task: class-dependent means + noise, one shared pool
    # all batch sizes draw from deterministically
    max_batch = base_batch * 8
    rng = np.random.RandomState(0)
    labels = rng.randint(0, nout, max_batch * 2)
    centers = rng.randn(nout, nin).astype(np.float32) * 2.0
    pool_x = (centers[labels] + rng.randn(len(labels), nin)).astype(np.float32)
    pool_y = np.eye(nout, dtype=np.float32)[labels]

    def run(trainer, batch, k=steps):
        idx = np.arange(len(pool_x))
        scores, pos = [], 0
        for _ in range(k):
            take = idx[pos:pos + batch]
            if len(take) < batch:
                pos = 0
                take = idx[:batch]
            pos += batch
            scores.append(float(trainer.fit_batch(pool_x[take], pool_y[take])))
        return float(np.mean(scores[-3:]))

    def timed(trainer, batch):
        take = np.arange(batch)
        x, y = pool_x[take], pool_y[take]
        trainer.fit_batch(x, y)  # compile
        _host_fence(trainer.params)

        def block():
            start = time.perf_counter()
            for _ in range(bench_steps):
                trainer.fit_batch(x, y)
            _host_fence(trainer.params)
            return time.perf_counter() - start

        rate, spread = _median_rate(block, bench_steps)
        return 1e3 / rate, spread  # ms/step

    # -- baseline: tuned small batch, plain Adam ---------------------------
    t_base = DistributedTrainer(build(Adam(1e-3)), mesh=mesh,
                                metrics_every=0)
    base_loss = run(t_base, base_batch)
    base_ms, _ = timed(t_base, base_batch)

    per_batch = [{"batch": base_batch, "updater": "Adam",
                  "final_loss": round(base_loss, 4),
                  "step_ms": round(base_ms, 3)}]

    # -- large batch: LAMB + warmup + distributed BN + bucketed exchange --
    bn_group = 2 if n % 2 == 0 and n > 1 else 1
    for scale in (2, 4, 8):
        batch = base_batch * scale
        lamb = Lamb(WarmupSchedule(warmup_iterations=max(steps // 8, 2),
                                   base_value=2e-2))
        t = DistributedTrainer(
            build(lamb), mesh=mesh, zero1=True, bn_group_size=bn_group,
            strategy=BucketedAllReduceSync(bucket_bytes=1 << 12),
            metrics_every=0)
        loss = run(t, batch)
        ms, _ = timed(t, batch)
        per_batch.append({"batch": batch, "updater": "Lamb+warmup",
                          "final_loss": round(loss, 4),
                          "step_ms": round(ms, 3)})
    big_loss = per_batch[-1]["final_loss"]

    # -- bucketed vs unbucketed at full DP width ---------------------------
    # bn_group_size=n pins BOTH paths to global batch statistics, so the
    # trajectory comparison isolates the exchange spelling
    batch = base_batch * 8
    t_sync = DistributedTrainer(build(Adam(1e-3)), mesh=mesh,
                                bn_group_size=n, metrics_every=0)
    t_buck = DistributedTrainer(build(Adam(1e-3)), mesh=mesh,
                                bn_group_size=n,
                                strategy=BucketedAllReduceSync(
                                    bucket_bytes=1 << 12),
                                metrics_every=0)
    traj_sync = [float(t_sync.fit_batch(pool_x[:batch], pool_y[:batch]))
                 for _ in range(4)]
    traj_buck = [float(t_buck.fit_batch(pool_x[:batch], pool_y[:batch]))
                 for _ in range(4)]
    sync_ms, sync_spread = timed(t_sync, batch)
    buck_ms, buck_spread = timed(t_buck, batch)
    comp = t_buck.compression_stats() or {}

    ratio = buck_ms / max(sync_ms, 1e-9)
    return {
        "n_devices": n,
        "bn_group_size": bn_group,
        "base_batch": base_batch,
        "max_batch": batch,
        "per_batch": per_batch,
        "large_batch_final_loss": big_loss,
        "baseline_final_loss": round(base_loss, 4),
        # 8x-batch LAMB recipe within tolerance of the tuned small-batch
        # Adam baseline (same step count; the claim is convergence does
        # not break, not that fewer samples suffice)
        "large_batch_loss_within_tolerance": bool(
            big_loss <= base_loss * 1.3 + 0.05),
        "step_ms_sync_allreduce": round(sync_ms, 3),
        "step_ms_bucketed": round(buck_ms, 3),
        "spread_sync": sync_spread,
        "spread_bucketed": buck_spread,
        "bucketed_step_ratio": round(ratio, 3),
        # CPU gate: no-regression with measurement headroom (the overlap
        # win itself needs a real DCN path)
        "bucketed_no_regression": bool(ratio <= 1.25),
        "bucketed_trajectory_exact": bool(np.allclose(
            traj_sync, traj_buck, rtol=1e-5)),
        "bucket_count": comp.get("buckets"),
        "bucket_volume_bytes": comp.get("bucket_volume_bytes"),
        "total_exchanged_bytes": comp.get("total_exchanged_bytes"),
    }


def measure_generate_decode(vocab: int = 512, hidden: int = 256,
                            layers: int = 4, heads: int = 8,
                            max_len: int = 512, batch: int = 8,
                            prompt_len: int = 32, decode_steps: int = 64,
                            warmup_steps: int = 4,
                            attn_len: int = None) -> dict:
    """Autoregressive decode row (ISSUE 9 acceptance): tokens/sec/chip at a
    FIXED batch through the KV-cached incremental path, the prefill-vs-
    decode millisecond split (the two phases TPU serving capacity planning
    provisions separately), and the flash-decode kernel vs the reference
    impl on the decode attention shapes. All decode steps share ONE
    compiled [B, 1] program — the static-shape cache contract."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.generate import GenerationSession
    from deeplearning4j_tpu.model.zoo import TransformerLM
    from deeplearning4j_tpu.ops import (decode_attention_reference,
                                        flash_decode_attention)

    model = TransformerLM(vocab_size=vocab, hidden=hidden, n_layers=layers,
                          n_heads=heads, max_len=max_len).init()
    sess = GenerationSession(model, max_len=max_len)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, vocab, prompt_len).tolist()
               for _ in range(batch)]

    def run_prefill():
        start = time.perf_counter()
        carry, logits, lens = sess.prefill(prompts)
        _host_fence(logits)
        return time.perf_counter() - start, carry, lens

    _, carry0, lens = run_prefill()  # compile
    prefill_ms = []
    for _ in range(REPEATS):
        sec, carry0, lens = run_prefill()
        prefill_ms.append(sec * 1e3)
    prefill_ms_med = statistics.median(prefill_ms)

    tokens = jnp.asarray(rng.randint(1, vocab, batch), jnp.int32)
    carry = carry0
    for _ in range(warmup_steps):  # compile + settle
        carry, logits = sess.decode(carry, tokens)
        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    _host_fence(tokens)

    def decode_block():
        nonlocal carry, tokens
        start = time.perf_counter()
        for _ in range(decode_steps):
            carry, logits = sess.decode(carry, tokens)
            tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        _host_fence(tokens)
        return time.perf_counter() - start

    rate, spread = _median_rate(decode_block, batch * decode_steps)
    decode_ms_per_token = 1e3 / (rate / batch)

    # flash decode kernel vs reference on the decode attention shapes
    L = attn_len or max_len
    d = hidden // heads
    q = jnp.asarray(rng.randn(batch, heads, 1, d), jnp.float32)
    k = jnp.asarray(rng.randn(batch, heads, L, d), jnp.float32)
    v = jnp.asarray(rng.randn(batch, heads, L, d), jnp.float32)
    pos = jnp.full((batch,), L - 1, jnp.int32)
    flash = jax.jit(lambda *a: flash_decode_attention(*a))
    ref = jax.jit(lambda *a: decode_attention_reference(*a))

    def attn_ms(fn, iters=16):
        _host_fence(fn(q, k, v, pos))
        vals = []
        for _ in range(REPEATS):
            start = time.perf_counter()
            for _ in range(iters):
                out = fn(q, k, v, pos)
            _host_fence(out)
            vals.append((time.perf_counter() - start) / iters * 1e3)
        return statistics.median(vals)

    ref_ms = attn_ms(ref)
    flash_ms = attn_ms(flash)

    on_tpu = jax.default_backend() == "tpu"
    return {
        "tokens_per_sec_per_chip": round(rate, 2),
        "tokens_per_sec_spread": spread,
        "batch": batch,
        "prompt_len": prompt_len,
        "max_len": max_len,
        "decode_steps": decode_steps,
        "prefill_ms": round(prefill_ms_med, 3),
        "decode_ms_per_token": round(decode_ms_per_token, 3),
        "prefill_vs_decode_ratio": round(
            prefill_ms_med / max(decode_ms_per_token, 1e-9), 2),
        "model": {"vocab": vocab, "hidden": hidden, "layers": layers,
                  "heads": heads},
        "decode_attn_ref_ms": round(ref_ms, 3),
        "decode_attn_flash_ms": round(flash_ms, 3),
        "flash_decode_speedup": round(ref_ms / max(flash_ms, 1e-9), 3),
        "note": ("flash kernel compiled on TPU" if on_tpu else
                 "flash kernel in Pallas interpret mode off-TPU — the "
                 "speedup column is only meaningful on the chip"),
    }


def measure_speculative_decode(vocab: int = 32, target_hidden: int = 256,
                               target_layers: int = 4,
                               draft_hidden: int = 32,
                               draft_layers: int = 1,
                               heads: int = 4, max_len: int = 64,
                               batch: int = 8, prompt_len: int = 8,
                               k: int = 6, spec_steps: int = 16,
                               target_train_steps: int = 100,
                               draft_train_steps: int = 400) -> dict:
    """Speculative decoding row (ISSUE 11 acceptance): accepted-tokens/
    step and tokens/sec for draft-propose/target-verify vs the plain
    KV-cached decode of the SAME target model (the ``generate_decode``
    path). Both models train briefly on a deterministic successor task so
    the draft actually agrees with the target (acceptance measures
    draft/target agreement, not task skill — exact acceptance sampling
    keeps the output law either way). The speculative step is ONE fused
    dispatch (k+1 chained draft forwards + one tq=k+1 target verify +
    accept + rewind), so each target-model serial round emits ~k+1 tokens
    instead of 1 — the per-token latency lever this row quantifies."""
    import numpy as np

    import jax.numpy as jnp

    from deeplearning4j_tpu.generate import (GenerationSession,
                                             SpeculativeGenerationSession)
    from deeplearning4j_tpu.model.zoo import TransformerLM
    from deeplearning4j_tpu.train.solver import Solver
    from deeplearning4j_tpu.train.updaters import Adam

    rng = np.random.RandomState(0)

    def make_batch(b, t):
        s = rng.randint(0, vocab, (b, 1))
        x = (s + np.arange(t)) % vocab
        return jnp.asarray(x, jnp.int32), jnp.asarray((x + 1) % vocab,
                                                      jnp.int32)

    def train(model, steps):
        sol = Solver(model)
        for _ in range(steps):
            x, y = make_batch(32, 16)
            sol.fit_batch(x, y)
        xp, yp = make_batch(16, 16)
        return float((jnp.argmax(model.output(xp), axis=1) == yp).mean())

    target = TransformerLM(vocab_size=vocab, hidden=target_hidden,
                           n_layers=target_layers, n_heads=heads,
                           max_len=max_len, updater=Adam(1e-3)).init()
    target_acc = train(target, target_train_steps)
    draft = TransformerLM(vocab_size=vocab, hidden=draft_hidden,
                          n_layers=draft_layers, n_heads=2, max_len=max_len,
                          seed=7, updater=Adam(5e-3)).init()
    draft_acc = train(draft, draft_train_steps)

    prompts = [((rng.randint(0, vocab) + np.arange(prompt_len))
                % vocab).tolist() for _ in range(batch)]

    # ---- baseline: plain greedy decode of the target (PR 9 path)
    plain = GenerationSession(target, max_len=max_len)
    carry, logits, _ = plain.prefill(prompts)
    toks = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(3):  # compile + settle
        carry, lg = plain.decode(carry, toks)
        toks = jnp.argmax(lg, -1).astype(jnp.int32)
    _host_fence(toks)

    def plain_block():
        nonlocal carry, toks
        start = time.perf_counter()
        for _ in range(spec_steps):
            carry, lg = plain.decode(carry, toks)
            toks = jnp.argmax(lg, -1).astype(jnp.int32)
        _host_fence(toks)
        return time.perf_counter() - start

    base_rate, base_spread = _median_rate(plain_block, batch * spec_steps)

    # ---- speculative: k proposals per fused step, greedy (exact)
    spec = SpeculativeGenerationSession(target, draft, max_len=max_len, k=k)
    tc, lg, _ = spec.target.prefill(prompts)
    dc, _, _ = spec.draft.prefill(prompts)
    seeds = jnp.zeros((batch,), jnp.uint32)
    gmask = jnp.ones((batch,), bool)
    temps = jnp.ones((batch,), jnp.float32)
    ks0 = jnp.zeros((batch,), jnp.int32)
    ps = jnp.ones((batch,), jnp.float32)
    state = {"steps": np.ones((batch,), np.int32),
             "last": np.asarray(jnp.argmax(lg, -1), np.int32),
             "tc": tc, "dc": dc, "emitted": 0, "accepted": 0}
    active = np.ones((batch,), bool)
    spec_ks = np.full((batch,), k, np.int32)

    def spec_block(record=True):
        start = time.perf_counter()
        for _ in range(spec_steps):
            state["tc"], state["dc"], toks2, n_acc, n_emit = spec.step(
                state["tc"], state["dc"], state["last"], state["steps"],
                active, seeds, gmask, temps, ks0, ps, spec_ks, k=k)
            ne = np.asarray(n_emit)
            state["last"] = np.asarray(toks2)[np.arange(batch), ne - 1]
            state["steps"] = state["steps"] + ne.astype(np.int32)
            if record:
                state["emitted"] += int(ne.sum())
                state["accepted"] += int(np.asarray(n_acc).sum())
        return time.perf_counter() - start

    spec_block(record=False)  # compile + settle
    # generation must stay clear of max_len across the timed repeats:
    # restart from fresh prefills each block
    durations = []
    emitted_per_block = None
    for _ in range(REPEATS):
        tc, lg, _ = spec.target.prefill(prompts)
        dc, _, _ = spec.draft.prefill(prompts)
        state.update(tc=tc, dc=dc, emitted=0, accepted=0,
                     steps=np.ones((batch,), np.int32),
                     last=np.asarray(jnp.argmax(lg, -1), np.int32))
        durations.append(spec_block())
        emitted_per_block = state["emitted"]
    sec = statistics.median(durations)
    spec_rate = emitted_per_block / sec
    proposed = batch * k * spec_steps
    accepted = state["accepted"]
    accepted_per_step = emitted_per_block / (spec_steps * batch)

    return {
        "tokens_per_sec_plain": round(base_rate, 2),
        "tokens_per_sec_plain_spread": base_spread,
        "tokens_per_sec_speculative": round(spec_rate, 2),
        "speculative_speedup": round(spec_rate / max(base_rate, 1e-9), 3),
        "accepted_tokens_per_step": round(accepted_per_step, 3),
        "acceptance_rate": round(accepted / max(proposed, 1), 3),
        "k": k,
        "batch": batch,
        "prompt_len": prompt_len,
        "target_model": {"vocab": vocab, "hidden": target_hidden,
                         "layers": target_layers, "heads": heads,
                         "train_accuracy": round(target_acc, 3)},
        "draft_model": {"hidden": draft_hidden, "layers": draft_layers,
                        "train_accuracy": round(draft_acc, 3)},
        "note": ("greedy speculative stream is token-identical to plain "
                 "greedy (exact acceptance sampling); speedup comes from "
                 "emitting ~accepted+1 tokens per target-model serial "
                 "round"),
    }


def measure_quantized_infer(batch: int = 64, n_in: int = 32,
                            hidden: int = 256, classes: int = 16,
                            train_steps: int = 60, infer_iters: int = 24,
                            holdout: int = 512,
                            match_gate: float = 0.98,
                            prob_mse_gate: float = 1e-4) -> dict:
    """Quantized-serving row (ISSUE 13 acceptance): quantized-vs-full-
    precision inference latency ratio for the int8 weight-only rewrite
    pass (per-channel absmax scales, dequant in the output epilogue),
    an ACCURACY-DELTA GATE on a calibration holdout (top-1 agreement +
    output MSE vs the full-precision model — the same gate a canary
    promotion should watch), plus the calibrated activation-quantization
    variant and fp8 where the jaxlib supports the dtype. On a CPU host
    the latency ratio is informational (no int8 matmul unit); the
    accuracy gate is the load-bearing check everywhere."""
    import numpy as np

    from deeplearning4j_tpu.nn import (Activation, InputType, LossFunction,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.rewrite import (QuantizeWeightsPass,
                                               calibrate, rewrite_model)
    from deeplearning4j_tpu.nn.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.train.updaters import Adam

    rng = np.random.RandomState(0)
    teacher = rng.randn(n_in, classes).astype(np.float32)

    def make_batch(n):
        x = rng.randn(n, n_in).astype(np.float32)
        y = np.eye(classes, dtype=np.float32)[np.argmax(x @ teacher, axis=1)]
        return x, y

    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer(n_in=n_in, n_out=hidden,
                              activation=Activation.RELU))
            .layer(DenseLayer(n_out=hidden, activation=Activation.RELU))
            .layer(OutputLayer(n_out=classes, loss=LossFunction.MCXENT,
                               activation=Activation.SOFTMAX))
            .set_input_type(InputType.feed_forward(n_in))
            .build())
    model = MultiLayerNetwork(conf).init()
    for _ in range(train_steps):
        model.fit(*make_batch(batch))
    xh, yh = make_batch(holdout)
    base_probs = np.asarray(model.output(xh))
    base_top1 = np.argmax(base_probs, axis=1)
    task_acc = float(np.mean(base_top1 == np.argmax(yh, axis=1)))

    def infer_ms(m) -> float:
        _host_fence(m.output(xh))  # compile
        vals = []
        for _ in range(REPEATS):
            start = time.perf_counter()
            for _ in range(infer_iters):
                out = m.output(xh)
            _host_fence(out)
            vals.append((time.perf_counter() - start) / infer_iters * 1e3)
        return statistics.median(vals)

    def variant(passes):
        m2, applied = rewrite_model(model, passes)
        probs = np.asarray(m2.output(xh))
        top1 = np.argmax(probs, axis=1)
        return {
            "applied": applied,
            "infer_ms": round(infer_ms(m2), 3),
            "top1_match_rate": round(float(np.mean(top1 == base_top1)), 4),
            "prob_mse": float(np.mean((probs - base_probs) ** 2)),
        }

    fp_ms = infer_ms(model)
    int8 = variant([QuantizeWeightsPass("int8")])
    ranges = calibrate(model, [make_batch(batch)[0] for _ in range(4)])
    int8_act = variant([QuantizeWeightsPass("int8", act_ranges=ranges)])
    try:
        fp8 = variant([QuantizeWeightsPass("fp8")])
    except ValueError as e:  # jaxlib without float8_e4m3fn
        fp8 = {"skipped": str(e)}

    accuracy_ok = (int8["top1_match_rate"] >= match_gate
                   and int8["prob_mse"] <= prob_mse_gate)
    return {
        "fp_infer_ms": round(fp_ms, 3),
        "int8_weight_only": int8,
        "int8_activations": int8_act,
        "fp8_weight_only": fp8,
        "quantized_speedup": round(fp_ms / max(int8["infer_ms"], 1e-9), 3),
        "calibration_batches": 4,
        "calibrated_layers": len(ranges),
        "task_accuracy_fp": round(task_acc, 4),
        "accuracy_gate": {"top1_match_min": match_gate,
                          "prob_mse_max": prob_mse_gate,
                          "ok": bool(accuracy_ok)},
        "batch": holdout,
        "model": {"n_in": n_in, "hidden": hidden, "classes": classes},
        "note": ("the latency ratio is only meaningful on hardware with "
                 "an int8 matmul path (TPU MXU); on CPU the row gates "
                 "accuracy of the exact rewrite that deploys via "
                 "ModelManager(optimize='inference:int8')"),
    }


def measure_int8_kv_cache(vocab: int = 32, hidden: int = 256,
                          layers: int = 2, heads: int = 4,
                          max_len: int = 128, batch: int = 4,
                          prompt_len: int = 8, gen_tokens: int = 48,
                          train_steps: int = 80,
                          match_gate: float = 0.95,
                          ratio_gate: float = 1.8) -> dict:
    """int8 KV cache row (ISSUE 13 acceptance): resident-sequences ratio
    at a fixed cache HBM budget (int8 cache + per-slot/per-head f32
    scales vs an fp16 cache — the gate is >= 1.8x) and a greedy-stream
    token-match-rate gate against the full-precision cache on the SAME
    trained model (quantization must not change what the model says).
    Tokens/sec both ways is informational (the dequant rides the decode
    attention; the win is resident bytes, not step time)."""
    import numpy as np

    import jax.numpy as jnp

    from deeplearning4j_tpu.generate import GenerationSession
    from deeplearning4j_tpu.model.zoo import TransformerLM
    from deeplearning4j_tpu.train.solver import Solver
    from deeplearning4j_tpu.train.updaters import Adam

    rng = np.random.RandomState(0)
    model = TransformerLM(vocab_size=vocab, hidden=hidden, n_layers=layers,
                          n_heads=heads, max_len=max_len,
                          updater=Adam(1e-3)).init()
    sol = Solver(model)
    for _ in range(train_steps):
        s = rng.randint(0, vocab, (16, 1))
        x = (s + np.arange(12)) % vocab
        sol.fit_batch(jnp.asarray(x, jnp.int32),
                      jnp.asarray((x + 1) % vocab, jnp.int32))

    prompts = [((rng.randint(0, vocab) + np.arange(prompt_len))
                % vocab).tolist() for _ in range(batch)]
    fp_sess = GenerationSession(model, max_len=max_len)
    q_sess = GenerationSession(model, max_len=max_len, cache_dtype="int8")

    def timed_generate(sess):
        sess.generate(prompts, 4, greedy=True)  # compile
        durations, out = [], None
        for _ in range(REPEATS):
            start = time.perf_counter()
            out = sess.generate(prompts, gen_tokens, greedy=True)
            durations.append(time.perf_counter() - start)
        n_tokens = sum(len(r) for r in out)
        return out, n_tokens / statistics.median(durations)

    fp_tokens, fp_rate = timed_generate(fp_sess)
    q_tokens, q_rate = timed_generate(q_sess)
    pairs = [(a, b) for ra, rb in zip(fp_tokens, q_tokens)
             for a, b in zip(ra, rb)]
    match_rate = float(np.mean([a == b for a, b in pairs]))

    # cache-byte accounting from the REAL carries: K/V leaves (+ scale
    # planes on the int8 side); the fp16 equivalent is the f32 K/V bytes
    # halved — the serving dtype this row's capacity claim is against
    def kv_bytes(sess):
        total = 0
        for st in sess.decode_state(1).values():
            for key, leaf in st.items():
                if key.startswith("cache_"):
                    total += leaf.size * leaf.dtype.itemsize
        return total

    fp32_bytes = kv_bytes(fp_sess)
    int8_bytes = kv_bytes(q_sess)
    fp16_bytes = fp32_bytes // 2
    resident_ratio = fp16_bytes / max(int8_bytes, 1)
    return {
        "kv_cache_bytes_per_seq_fp32": int(fp32_bytes),
        "kv_cache_bytes_per_seq_fp16_equiv": int(fp16_bytes),
        "kv_cache_bytes_per_seq_int8": int(int8_bytes),
        "resident_seqs_ratio_vs_fp16": round(resident_ratio, 3),
        "resident_ratio_gate": {"min": ratio_gate,
                                "ok": bool(resident_ratio >= ratio_gate)},
        "greedy_token_match_rate": round(match_rate, 4),
        "token_match_gate": {"min": match_gate,
                             "ok": bool(match_rate >= match_gate)},
        "tokens_per_sec_fp_cache": round(fp_rate, 2),
        "tokens_per_sec_int8_cache": round(q_rate, 2),
        "generated_tokens_compared": len(pairs),
        "batch": batch,
        "model": {"vocab": vocab, "hidden": hidden, "layers": layers,
                  "heads": heads, "head_dim": hidden // heads,
                  "max_len": max_len},
        "note": ("per-slot scale overhead is 4 bytes per cached position "
                 "per head, so the fp16-relative ratio is 2d/(d+4) — "
                 ">= 1.8x needs head_dim >= 64; the dequant runs inside "
                 "decode_attention's reference path (the resident cache "
                 "stays int8 in HBM)"),
    }


def measure_engine_pool_scaling(n_requests: int = 240, threads: int = 4,
                                replicas: int = 4, distinct_payloads: int = 8,
                                overload_requests: int = 120) -> dict:
    """Replica-pool serving row (ISSUE 10 acceptance): sustained RPS
    through EnginePool at 1 vs N replicas (pool dispatch overhead at N=1
    vs a bare engine must stay <10%; scaling is only meaningful where
    cores allow — this host's count is reported), cache hit-rate speedup
    on a repeated-payload workload, and shed-by-priority counts under a
    forced overload — with every signal checked visible on /metrics."""
    import itertools as _it
    import threading as _th

    import numpy as np

    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.obs.metrics import MetricsRegistry
    from deeplearning4j_tpu.obs.prom import render_prometheus
    from deeplearning4j_tpu.parallel import EnginePool, ParallelInference

    conf = (NeuralNetConfiguration.builder().seed(5).list()
            .layer(DenseLayer(n_in=8, n_out=16))
            .layer(OutputLayer(n_in=16, n_out=4))
            .build())
    model = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    payloads = [rng.randn(1, 8).astype(np.float32)
                for _ in range(max(threads, 16))]

    def hammer(submit, n, nthreads) -> float:
        """Sustained RPS: nthreads callers drain a shared request
        counter; returns the median rate of REPEATS passes."""
        def one_pass():
            counter = _it.count()
            errs = []

            def worker():
                while True:
                    i = next(counter)
                    if i >= n:
                        return
                    try:
                        submit(payloads[i % len(payloads)])
                    except Exception as e:  # noqa: BLE001
                        errs.append(e)
                        return
            ts = [_th.Thread(target=worker) for _ in range(nthreads)]
            start = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if errs:
                raise errs[0]
            return n / (time.perf_counter() - start)
        return statistics.median(one_pass() for _ in range(REPEATS))

    # batch_limit=1 keeps every forward on ONE compiled shape, so the row
    # measures dispatch overhead, not recompiles
    eng_kw = dict(batch_limit=1, workers=1, queue_limit=512)

    # ---- bare engine baseline vs pool at N=1 (dispatch overhead) -------
    bare = ParallelInference(model, registry=MetricsRegistry(),
                             name="bench-bare", **eng_kw)
    bare.output(payloads[0])  # compile
    bare_rps = hammer(lambda x: bare.output(x), n_requests, threads)
    bare.shutdown(drain=False)

    pool1 = EnginePool(model=model, replicas=1, registry=MetricsRegistry(),
                       name="bench-p1", **eng_kw)
    pool1.output(payloads[0])
    pool1_rps = hammer(lambda x: pool1.output(x), n_requests, threads)
    pool1.shutdown(drain=False)

    # ---- N replicas ----------------------------------------------------
    regN = MetricsRegistry()
    poolN = EnginePool(model=model, replicas=replicas, registry=regN,
                       name="bench-pN", **eng_kw)
    for _ in range(replicas * 2):  # compile every replica's forward
        poolN.output(payloads[0])
    poolN_rps = hammer(lambda x: poolN.output(x), n_requests,
                       max(threads, replicas))
    dispatchedN = poolN.stats()["dispatched"]
    poolN.shutdown(drain=False)

    # ---- cache hit-rate speedup on a repeated-payload workload ---------
    hot = payloads[:distinct_payloads]
    reg_c = MetricsRegistry()
    cpool = EnginePool(model=model, replicas=1, registry=reg_c,
                       cache_entries=256, cache_ttl=600.0,
                       name="bench-cache", **eng_kw)
    cpool.output(hot[0])
    cold_rps = hammer(lambda x: cpool.output(x, use_cache=False),
                      n_requests, threads)
    warm_rps = hammer(
        lambda x: cpool.output(hot[hash(x.tobytes()) % len(hot)]),
        n_requests, threads)
    cache_stats = cpool.stats()["cache"]
    cpool.shutdown(drain=False)

    # ---- forced overload: shed order by priority -----------------------
    reg_o = MetricsRegistry()
    opool = EnginePool(model=model, replicas=2, registry=reg_o,
                       max_pending=8,
                       priorities={"high": 1.0, "low": 0.5},
                       name="bench-over", **eng_kw)
    opool.output(payloads[0])
    shed_errs = _it.count()

    def flood(priority):
        for i in range(overload_requests // (2 * threads)):
            try:
                opool.output_async(payloads[i % len(payloads)],
                                   priority=priority, use_cache=False)
            except Exception:  # noqa: BLE001 — shed, counted below
                next(shed_errs)
    ts = [_th.Thread(target=flood, args=("low" if i % 2 else "high",))
          for i in range(2 * threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    opool.drain(timeout=30)
    shed_by_priority = opool.stats().get("shed_by_priority", {})
    # the acceptance surface: all of it must be scrapeable
    prom = render_prometheus(reg_o) + render_prometheus(reg_c) \
        + render_prometheus(regN)
    metrics_visible = all(s in prom for s in (
        "dl4j_tpu_pool_dispatch_total", "dl4j_tpu_pool_load_imbalance",
        "dl4j_tpu_pool_cache_events_total", "dl4j_tpu_pool_shed_total",
        "dl4j_tpu_inference_effective_batch_limit",
        "dl4j_tpu_inference_flush_timeout_seconds"))
    opool.shutdown(drain=False)

    return {
        "bare_engine_rps": round(bare_rps, 1),
        "pool_1_replica_rps": round(pool1_rps, 1),
        "pool_overhead_at_1": round(1.0 - pool1_rps / bare_rps, 4),
        "pool_n_replicas": replicas,
        "pool_n_rps": round(poolN_rps, 1),
        "pool_scaling_vs_1": round(poolN_rps / pool1_rps, 2),
        "pool_n_dispatch_spread": {k: int(v) for k, v in
                                   sorted(dispatchedN.items())},
        "host_cpu_count": os.cpu_count(),
        "cache_off_rps": round(cold_rps, 1),
        "cache_on_repeated_rps": round(warm_rps, 1),
        "cache_speedup": round(warm_rps / cold_rps, 2),
        "cache_hit_rate": (round(cache_stats["hit_rate"], 4)
                           if cache_stats["hit_rate"] is not None else None),
        "overload_shed_by_priority": shed_by_priority,
        "metrics_visible": metrics_visible,
        "note": ("near-linear replica scaling requires >= N cores; on a "
                 "1-core host the row validates overhead + shed order + "
                 "cache, not parallel speedup"),
    }


def measure_fabric_overhead(n_requests: int = 120, threads: int = 4) -> dict:
    """Cross-host fabric row (ISSUE 12 acceptance): RPS of a direct
    JsonRemoteInference client against one HTTP host vs the same host
    fronted by an EnginePool with a single RemoteReplica (the fabric
    adds a dispatch + executor hop per request; the gate is < 10%
    overhead at N=1), with the fabric metric series checked visible."""
    import itertools as _it
    import threading as _th

    import numpy as np

    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.obs.metrics import MetricsRegistry
    from deeplearning4j_tpu.obs.prom import render_prometheus
    from deeplearning4j_tpu.parallel import EnginePool
    from deeplearning4j_tpu.remote import (JsonModelServer,
                                           JsonRemoteInference,
                                           RemoteReplica)

    conf = (NeuralNetConfiguration.builder().seed(5).list()
            .layer(DenseLayer(n_in=8, n_out=16))
            .layer(OutputLayer(n_in=16, n_out=4))
            .build())
    model = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    payloads = [rng.randn(1, 8).astype(np.float32) for _ in range(16)]

    def one_pass(submit, n, nthreads) -> float:
        counter = _it.count()
        errs = []

        def worker():
            while True:
                i = next(counter)
                if i >= n:
                    return
                try:
                    submit(payloads[i % len(payloads)])
                except Exception as e:  # noqa: BLE001
                    errs.append(e)
                    return
        ts = [_th.Thread(target=worker) for _ in range(nthreads)]
        start = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if errs:
            raise errs[0]
        return n / (time.perf_counter() - start)

    host = JsonModelServer(model, port=0, workers=1, batch_limit=1,
                           queue_limit=512, registry=MetricsRegistry(),
                           name="fab-bench-host").start()
    endpoint = f"http://127.0.0.1:{host.port}/v1/serving"
    try:
        client = JsonRemoteInference(endpoint, registry=MetricsRegistry())
        client.predict(payloads[0])  # compile the host's forward
        fab_reg = MetricsRegistry()
        pool = EnginePool(
            engines=[RemoteReplica(endpoint, name="fab-bench-rr",
                                   probe_interval=0.5, registry=fab_reg)],
            registry=fab_reg, name="fab-bench")
        try:
            pool.output(payloads[0], timeout=30)
            # paired interleaved passes (the tracing_overhead recipe for
            # this noisy 1-core host): alternate direct/fabric so host
            # drift cancels inside each pair, take the median per-pair
            # ratio and the median RPS of each leg
            directs, fabrics, ratios = [], [], []
            for _ in range(max(REPEATS, 5)):
                d = one_pass(lambda x: client.predict(x),
                             n_requests, threads)
                f = one_pass(lambda x: pool.output(x, timeout=30),
                             n_requests, threads)
                directs.append(d)
                fabrics.append(f)
                ratios.append(f / d)
            direct_rps = statistics.median(directs)
            fabric_rps = statistics.median(fabrics)
            ratio = statistics.median(ratios)
            prom = render_prometheus(fab_reg)
            metrics_visible = all(s in prom for s in (
                "dl4j_tpu_fabric_probe_total",
                "dl4j_tpu_fabric_replica_healthy",
                "dl4j_tpu_fabric_request_latency_seconds",
                "dl4j_tpu_fabric_failover_total"))
        finally:
            pool.shutdown(drain=False)
    finally:
        host.stop(drain=False)

    overhead = 1.0 - ratio
    return {
        "direct_client_rps": round(direct_rps, 1),
        "fabric_pool_1_rps": round(fabric_rps, 1),
        "fabric_overhead_at_1": round(overhead, 4),
        "fabric_overhead_under_10pct": bool(overhead < 0.10),
        "metrics_visible": metrics_visible,
        "note": ("both legs pay the same HTTP round trip to the host; "
                 "the delta is the fabric's dispatch + executor hop"),
    }


def measure_checkpoint_stall(nin: int = 256, hidden: int = 512,
                             nout: int = 64, batch: int = 64,
                             warmup_steps: int = 3, steps: int = 12,
                             save_every: int = 1) -> dict:
    """Fault-tolerant-training row (ISSUE 15 acceptance): the per-step
    STALL checkpointing puts on the step critical path — measured as the
    time spent inside the CheckpointListener's ``iteration_done`` hook —
    for sync saves (serialize + fsync + pointer flip on the step thread)
    vs async saves (device fetch + enqueue; a bounded daemon writer does
    the rest). Gate: async stall < 20% of the sync stall. Second gate:
    an injected ``checkpoint.write`` fault NEVER aborts fit — the
    failure is counted and training continues."""
    import shutil
    import tempfile

    import numpy as np

    from deeplearning4j_tpu.core.listeners import TrainingListener
    from deeplearning4j_tpu.core.resilience import (
        FaultInjector, set_fault_injector)
    from deeplearning4j_tpu.nn import (
        Activation, InputType, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.obs.metrics import MetricsRegistry
    from deeplearning4j_tpu.train.checkpoint import (
        CHECKPOINT_WRITE_SITE, CheckpointListener)
    from deeplearning4j_tpu.train.solver import Solver
    from deeplearning4j_tpu.train.updaters import Adam

    rng = np.random.RandomState(0)
    x = rng.rand(batch, nin).astype(np.float32)
    y = np.eye(nout, dtype=np.float32)[rng.randint(0, nout, batch)]

    def build():
        conf = (NeuralNetConfiguration.builder().seed(5).updater(Adam(1e-3))
                .list()
                .layer(DenseLayer(n_out=hidden, activation=Activation.RELU))
                .layer(DenseLayer(n_out=hidden, activation=Activation.RELU))
                .layer(OutputLayer(n_out=nout, loss=LossFunction.MCXENT,
                                   activation=Activation.SOFTMAX))
                .set_input_type(InputType.feed_forward(nin)).build())
        return MultiLayerNetwork(conf).init()

    class _TimedHook(TrainingListener):
        """Times the wrapped checkpoint listener's hook — the exact
        critical-path cost the async writer is supposed to remove."""

        def __init__(self, inner=None):
            self.inner = inner
            self.hook_s = []

        def iteration_done(self, model, iteration, epoch, score):
            t0 = time.perf_counter()
            if self.inner is not None:
                self.inner.iteration_done(model, iteration, epoch, score)
            self.hook_s.append(time.perf_counter() - t0)

    def run(mode):
        d = tempfile.mkdtemp(prefix=f"ckpt_stall_{mode}_")
        reg = MetricsRegistry()
        model = build()
        solver = Solver(model)
        model._trainer = solver
        inner = None
        if mode != "none":
            inner = CheckpointListener(
                d, save_every_n_iterations=save_every,
                async_save=(mode == "async"), registry=reg,
                log_fn=lambda m: None)
        hook = _TimedHook(inner)
        model.add_listeners(hook)
        step_s = []
        for i in range(warmup_steps + steps):
            t0 = time.perf_counter()
            model.fit(x, y, epochs=1)
            step_s.append(time.perf_counter() - t0)
        if inner is not None:
            inner.close()
        shutil.rmtree(d, ignore_errors=True)
        saved = ((warmup_steps + steps) // save_every) if inner else 0
        return {
            "hook_ms": 1e3 * float(np.median(hook.hook_s[warmup_steps:])),
            "step_ms": 1e3 * float(np.median(step_s[warmup_steps:])),
            "saves": saved,
        }

    none_r = run("none")
    sync_r = run("sync")
    async_r = run("async")
    sync_stall = max(sync_r["hook_ms"] - none_r["hook_ms"], 1e-6)
    async_stall = max(async_r["hook_ms"] - none_r["hook_ms"], 0.0)

    # fault leg: an armed checkpoint.write fault must not abort fit
    d = tempfile.mkdtemp(prefix="ckpt_stall_fault_")
    reg = MetricsRegistry()
    model = build()
    model._trainer = Solver(model)
    ck = CheckpointListener(d, save_every_n_iterations=1, registry=reg,
                            log_fn=lambda m: None)
    model.add_listeners(ck)
    inj = FaultInjector()
    inj.inject_error(CHECKPOINT_WRITE_SITE,
                     lambda: OSError("injected disk failure"), times=2)
    prev = set_fault_injector(inj)
    try:
        fit_survived = True
        try:
            for _ in range(4):
                model.fit(x, y, epochs=1)
        except BaseException:
            fit_survived = False
    finally:
        set_fault_injector(prev)
    failures = reg.counter(
        "dl4j_tpu_training_checkpoint_failures_total", "").value
    saves_after_fault = reg.counter(
        "dl4j_tpu_training_checkpoint_saves_total", "", ("mode",)
    ).labels("sync").value
    ck.close()
    shutil.rmtree(d, ignore_errors=True)

    return {
        "step_ms_no_checkpoint": round(none_r["step_ms"], 3),
        "step_ms_sync_save": round(sync_r["step_ms"], 3),
        "step_ms_async_save": round(async_r["step_ms"], 3),
        "hook_ms_no_checkpoint": round(none_r["hook_ms"], 4),
        "hook_ms_sync_save": round(sync_r["hook_ms"], 3),
        "hook_ms_async_save": round(async_r["hook_ms"], 3),
        "sync_stall_ms": round(sync_stall, 3),
        "async_stall_ms": round(async_stall, 3),
        "async_vs_sync_stall_ratio": round(async_stall / sync_stall, 4),
        "async_checkpoint_stall_under_20pct": bool(
            async_stall < 0.2 * sync_stall),
        "injected_faults": int(inj.fired(CHECKPOINT_WRITE_SITE)),
        "checkpoint_failures_counted": int(failures),
        "saves_after_fault": int(saves_after_fault),
        "checkpoint_fault_never_aborts_fit": bool(
            fit_survived and failures == 2 and saves_after_fault == 2),
        "note": ("stall = time inside the checkpoint listener's "
                 "iteration_done hook (the step critical path); async "
                 "pays one device fetch + enqueue, sync pays serialize "
                 "+ fsync + pointer flip"),
    }


def measure_elastic_goodput(total_iters: int = 320,
                            pace_s: float = 0.25) -> dict:
    """Elastic-resize goodput row (ISSUE 16 acceptance): a real
    supervised ZeRO-1 trainer under scripted churn — one SIGKILL at full
    width plus one SIGTERM preemption whose reboot comes back at half
    the device count — must keep goodput ratio > 0.90, with every
    downtime second itemized by reason in the supervisor's ledger
    (backoff / stall / crash / preempted / reshard)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_elastic_resize_contract",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools",
                     "check_elastic_resize_contract.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    res = mod.run_goodput_churn(log=lambda m: None,
                                total_iters=total_iters, pace_s=pace_s)
    gp = res["goodput"]
    return {
        "metric": "training goodput under scripted churn "
                  "(one SIGKILL + one preemption-with-resize)",
        "total_iters": total_iters,
        "pace_s": pace_s,
        "goodput_ratio": round(gp["ratio"], 4),
        "wall_seconds": round(gp["wall_seconds"], 2),
        "useful_seconds": round(gp["useful_seconds"], 2),
        "downtime_seconds": {k: round(v, 3)
                             for k, v in gp["downtime_seconds"].items()},
        "restarts": res["restarts"],
        "preemptions": res["preemptions"],
        "child_rcs": res["churn"]["rcs"],
        "boot_widths": res["churn"]["widths"],
        "completed": bool(res["ok"]),
        "goodput_gt_0p90": bool(res["ok"] and gp["ratio"] > 0.90),
        "note": ("ratio = useful seconds / wall seconds over the whole "
                 "supervised run; downtime itemizes restart backoff, "
                 "heartbeat-aged stall/crash loss, and restore-to-first-"
                 "beat boot time (priced as 'reshard' when the width "
                 "changed)"),
    }


def measure_paged_kv_occupancy(vocab: int = 23, hidden: int = 32,
                               layers: int = 2, heads: int = 4,
                               max_len: int = 32, block_size: int = 4,
                               static_slots: int = 4,
                               paged_slots: int = 16,
                               n_requests: int = 12,
                               prompt_len: int = 5, gen_tokens: int = 6,
                               ratio_gate: float = 1.5,
                               match_gate: float = 1.0) -> dict:
    """Paged-KV occupancy row (ISSUE 17 acceptance): peak RESIDENT
    sequences under a short-sequence burst at a fixed KV HBM budget —
    a static slot x max_len DecodeEngine vs the paged engine whose block
    pool holds the SAME bytes (static_slots * max_len / block_size
    blocks, + the reserved trash block). Short rows only pin the blocks
    they touch, so the paged engine packs more concurrent streams into
    the same cache memory (the vLLM capacity claim); the gate is >= 1.5x
    measured peak residency, with greedy streams token-identical to the
    static engine (paging must not change what the model says)."""
    import numpy as np

    from deeplearning4j_tpu.model.zoo import TransformerLM
    from deeplearning4j_tpu.obs.metrics import MetricsRegistry
    from deeplearning4j_tpu.parallel.decode import DecodeEngine

    lm = TransformerLM(vocab_size=vocab, hidden=hidden, n_layers=layers,
                       n_heads=heads, max_len=max_len).init()
    rng = np.random.RandomState(0)
    prompts = [[int(t) for t in rng.randint(1, vocab, size=prompt_len)]
               for _ in range(n_requests)]
    # equal-HBM pool: exactly the static engine's block count (+1 for
    # the reserved trash block, which holds no sequence data)
    pool_blocks = static_slots * (max_len // block_size) + 1

    def burst(eng):
        peak = {"rows": 0, "blocks": 0}

        def hook():
            st = eng.stats()
            peak["rows"] = max(peak["rows"], int(eng._active.sum()))
            if st["kv_blocks_total"] is not None:
                peak["blocks"] = max(
                    peak["blocks"],
                    st["kv_blocks_total"] - st["kv_blocks_free"])
        eng._step_hook = hook
        try:
            hs = [eng.submit(p, max_tokens=gen_tokens) for p in prompts]
            return [h.result(timeout=300) for h in hs], peak
        finally:
            eng.shutdown()

    static_tokens, static_peak = burst(
        DecodeEngine(lm, max_len=max_len, slots=static_slots,
                     registry=MetricsRegistry(), name="kv-bench-static"))
    paged_tokens, paged_peak = burst(
        DecodeEngine(lm, max_len=max_len, slots=paged_slots,
                     block_size=block_size, num_kv_blocks=pool_blocks,
                     registry=MetricsRegistry(), name="kv-bench-paged"))

    pairs = [(a, b) for ra, rb in zip(static_tokens, paged_tokens)
             for a, b in zip(ra, rb)]
    match_rate = float(np.mean([a == b for a, b in pairs]))
    ratio = paged_peak["rows"] / max(static_peak["rows"], 1)
    return {
        "kv_pool_blocks": pool_blocks - 1,
        "block_size": block_size,
        "static_peak_resident_seqs": static_peak["rows"],
        "paged_peak_resident_seqs": paged_peak["rows"],
        "paged_peak_blocks_used": paged_peak["blocks"],
        "paged_occupancy_ratio": round(ratio, 3),
        "occupancy_ratio_gate": {"min": ratio_gate,
                                 "ok": bool(ratio >= ratio_gate)},
        "greedy_token_match_rate": round(match_rate, 4),
        "token_match_gate": {"min": match_gate,
                             "ok": bool(match_rate >= match_gate)},
        "note": (f"{n_requests} short requests (prompt {prompt_len} + "
                 f"{gen_tokens} generated) against the KV bytes of "
                 f"{static_slots} static slots x max_len {max_len}; "
                 "paged rows pin only the blocks they touch"),
    }


def measure_disagg_handoff(vocab: int = 23, hidden: int = 32,
                           layers: int = 2, heads: int = 4,
                           max_len: int = 32, prompt_len: int = 6,
                           gen_tokens: int = 8,
                           match_gate: float = 1.0) -> dict:
    """Disaggregated prefill/decode handoff row (ISSUE 17 acceptance):
    the wire cost of splitting the two serving phases — serialized
    handoff bytes for one request's cache state, and prefill-to-first-
    token latency through the full hop (prefill on a PrefillEngine,
    serialize, deserialize, resume on a paged DecodeEngine) vs the same
    model decoding unified. The resumed stream must be token-identical
    to unbroken local generation (gate: match rate >= 1.0); latency and
    bytes are the numbers a deployment sizes its fabric against."""
    import numpy as np

    from deeplearning4j_tpu.model.zoo import TransformerLM
    from deeplearning4j_tpu.obs.metrics import MetricsRegistry
    from deeplearning4j_tpu.parallel.decode import DecodeEngine
    from deeplearning4j_tpu.serving.disagg import (PrefillEngine,
                                                   deserialize_handoff,
                                                   serialize_handoff)

    lm = TransformerLM(vocab_size=vocab, hidden=hidden, n_layers=layers,
                       n_heads=heads, max_len=max_len).init()
    rng = np.random.RandomState(0)
    prompt = [int(t) for t in rng.randint(1, vocab, size=prompt_len)]

    pe = PrefillEngine(lm, max_len=max_len, registry=MetricsRegistry(),
                       name="disagg-bench-pre")
    eng = DecodeEngine(lm, max_len=max_len, slots=4, block_size=4,
                       registry=MetricsRegistry(),
                       name="disagg-bench-dec")

    def first_token_latency(start_fn):
        start = time.perf_counter()
        handle = start_fn()
        for ev in handle.events(timeout=120):
            if "token" in ev:
                break
        latency = time.perf_counter() - start
        handle.result(timeout=120)  # drain the stream before reuse
        return latency

    try:
        # unified baseline: prefill + decode on one engine
        first_token_latency(lambda: eng.submit(prompt,
                                               max_tokens=gen_tokens))
        unified = statistics.median(
            first_token_latency(
                lambda: eng.submit(prompt, max_tokens=gen_tokens))
            for _ in range(REPEATS))

        # disaggregated hop: prefill -> bytes -> resume
        wire = serialize_handoff(pe.prefill(prompt,
                                            max_tokens=gen_tokens))
        handoff_bytes = len(wire)
        first_token_latency(
            lambda: eng.submit_prefilled(deserialize_handoff(wire)))

        def two_hop():
            start = time.perf_counter()
            w = serialize_handoff(pe.prefill(prompt,
                                             max_tokens=gen_tokens))
            handle = eng.submit_prefilled(deserialize_handoff(w))
            for ev in handle.events(timeout=120):
                if "token" in ev:
                    break
            latency = time.perf_counter() - start
            return latency, handle.result(timeout=120)

        latencies, resumed = [], None
        for _ in range(REPEATS):
            latency, resumed = two_hop()
            latencies.append(latency)
        disagg = statistics.median(latencies)
        local = eng.submit(prompt, max_tokens=gen_tokens).result(
            timeout=120)
    finally:
        eng.shutdown()

    match_rate = float(np.mean([a == b
                                for a, b in zip(local, resumed)]))
    return {
        "handoff_bytes": handoff_bytes,
        "handoff_bytes_per_prompt_token": round(
            handoff_bytes / prompt_len, 1),
        "prefill_to_first_token_s_disagg": round(disagg, 4),
        "prefill_to_first_token_s_unified": round(unified, 4),
        "handoff_overhead_s": round(disagg - unified, 4),
        "resumed_token_match_rate": round(match_rate, 4),
        "token_match_gate": {"min": match_gate,
                             "ok": bool(match_rate >= match_gate)},
        "note": ("in-process hop: serialize + deserialize are on the "
                 "timed path, the network is not — wire time adds "
                 "handoff_bytes / fabric bandwidth"),
    }


def measure_model_multiplex(n_models: int = 8, warm_target: int = 4,
                            hot_requests: int = 120,
                            churn_requests: int = 10,
                            feat: int = 6,
                            served_ratio_gate: float = 2.0,
                            pagein_deadline_s: float = 60.0) -> dict:
    """Multi-tenant multiplexing row (ISSUE 19 acceptance): models
    served behind ONE host at a FIXED byte budget — the multiplexer
    (LRU/EWMA weight paging via ``ModelManager.park()``) vs the naive
    always-warm baseline that can only admit ``budget // model_bytes``
    models and must refuse the rest. Gate: >= 2x registered-models-
    served at equal budget, with every cold-start miss queued inside
    the page-in deadline (bounded and counted, never 503'd). Also
    reports cold-start p99 and the hot-tenant p99 delta between a quiet
    pool and one churning with cold-tenant page-ins — the SLO isolation
    number."""
    import tempfile
    import threading

    import numpy as np

    from deeplearning4j_tpu.nn import (
        MultiLayerNetwork,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.obs.metrics import MetricsRegistry
    from deeplearning4j_tpu.serving import ModelMultiplexer, ModelStore

    def build_model(s):
        conf = (NeuralNetConfiguration.builder().seed(s).list()
                .layer(DenseLayer(n_in=feat, n_out=12))
                .layer(OutputLayer(n_in=12, n_out=4))
                .build())
        return MultiLayerNetwork(conf).init()

    store = ModelStore(
        os.path.join(tempfile.mkdtemp(prefix="mux-bench-"), "registry"))
    for i in range(n_models):
        store.publish(f"m{i}", build_model(100 + i))
    x = np.linspace(-1.0, 1.0, feat, dtype=np.float32).reshape(1, feat)
    defaults = dict(workers=1, batch_limit=4, probation_seconds=0.0,
                    warmup_example=x)

    def p99(samples):
        s = sorted(samples)
        return s[min(len(s) - 1, int(round(0.99 * (len(s) - 1))))] \
            if s else 0.0

    # one measured model sizes the budget: room for warm_target warm
    probe = ModelMultiplexer(store, budget_bytes=1 << 40,
                             registry=MetricsRegistry(),
                             manager_defaults=defaults)
    probe.register("m0")
    probe.ensure_resident("m0")
    per_model = probe.resident_bytes()
    probe.shutdown(drain=False)
    budget = int(per_model * (warm_target + 0.5))

    # naive always-warm baseline at the SAME budget: greedy fill, every
    # model past the budget is refused (today's pre-paging behavior —
    # resident count capped by memory, not traffic)
    naive_served = min(n_models, budget // per_model)

    reg = MetricsRegistry()
    mux = ModelMultiplexer(
        store, budget_bytes=budget, registry=reg,
        default_pagein_deadline_s=pagein_deadline_s,
        manager_defaults=defaults)
    for i in range(n_models):
        mux.register(f"m{i}")
    try:
        # serve every registered model once; time the cold-start misses
        cold_lat, served, resident_peak = [], 0, 0
        for i in range(n_models):
            t0 = time.perf_counter()
            np.asarray(mux.output(f"m{i}", x, timeout=pagein_deadline_s))
            cold_lat.append(time.perf_counter() - t0)
            served += 1
            resident_peak = max(resident_peak,
                                mux.describe()["resident_models"])
        d = mux.describe()
        misses = sum(m["coldstart_misses"] for m in d["models"].values())
        evictions = sum(m["evictions"] for m in d["models"].values())

        # hot-tenant p99, quiet pool vs cold-tenant page-in churn
        def hot_pass(n):
            lat = []
            for _ in range(n):
                t0 = time.perf_counter()
                np.asarray(mux.output("m0", x, timeout=30.0))
                lat.append(time.perf_counter() - t0)
            return lat

        hot_pass(10)  # settle: m0 warm, jit hot
        quiet = hot_pass(hot_requests)
        stop = threading.Event()

        def churn():
            i, cold = 0, [f"m{i}" for i in range(2, n_models)]
            while not stop.is_set() and i < churn_requests:
                np.asarray(mux.output(cold[i % len(cold)], x,
                                      timeout=pagein_deadline_s))
                i += 1

        churner = threading.Thread(target=churn)
        churner.start()
        loud = hot_pass(hot_requests)
        stop.set()
        churner.join()
        ratio = served / max(1, naive_served)
        return {
            "metric": "registered models served behind one host at a "
                      "fixed byte budget (weight paging vs always-warm)",
            "budget_bytes": budget,
            "per_model_bytes": per_model,
            "models_registered": n_models,
            "models_served_multiplexed": served,
            "models_served_always_warm": int(naive_served),
            "served_ratio": round(ratio, 3),
            "served_ratio_gate": {"min": served_ratio_gate,
                                  "ratio": round(ratio, 3),
                                  "ok": bool(ratio >= served_ratio_gate)},
            "resident_models_peak": resident_peak,
            "resident_within_budget": bool(resident_peak <= warm_target),
            "coldstart_misses": int(misses),
            "coldstart_bounded": bool(
                misses == n_models
                and max(cold_lat) <= pagein_deadline_s),
            "coldstart_p99_ms": round(p99(cold_lat) * 1e3, 2),
            "evictions": int(evictions),
            "hot_p99_ms_quiet": round(p99(quiet) * 1e3, 3),
            "hot_p99_ms_under_churn": round(p99(loud) * 1e3, 3),
            "hot_p99_delta_ms": round(
                (p99(loud) - p99(quiet)) * 1e3, 3),
            "note": ("baseline admits budget // model_bytes models and "
                     "refuses the rest; the multiplexer serves every "
                     "registered model by paging LRU/EWMA victims out "
                     "(drain-first — no request is lost to eviction). "
                     "Hot delta is the SLO-isolation number: hot-model "
                     "requests while cold tenants force page-in churn."),
        }
    finally:
        mux.shutdown(drain=False)


def measure_pipeline_bubble_share(n_stages: int = 4, n_micro: int = 8,
                                  n_blocks: int = 8, nin: int = 16,
                                  hidden: int = 64, nout: int = 8,
                                  warmup_steps: int = 1, bench_steps: int = 4,
                                  bubble_gate: float = 0.35,
                                  force_devices: int = 0) -> dict:
    """Pipeline-parallel row (ISSUE 20 acceptance): the analytic bubble
    share (S-1)/(M+S-1) of both tick schedules at (S, M), the resident-
    microbatch contrast (1F1B's min(S, M) vs GPipe's M — the memory story
    that lets M grow to shrink the bubble), fenced step time for both
    schedules on a pipe=S mesh, and the <0.35 bubble gate at the
    S=4/M=8/1F1B operating point. Trajectory equality vs the single-device
    Solver is a tier-1 test (test_pipeline_trainer.py), not re-proven
    here. ``force_devices`` forces N virtual host devices for the CPU
    spelling (must land before backend init)."""
    if force_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={force_devices}"
            ).strip()

    import numpy as np

    from deeplearning4j_tpu.nn import (
        Activation, InputType, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.parallel import (PipelineParallelTrainer,
                                             make_mesh)
    from deeplearning4j_tpu.parallel.pipeline import build_pipeline_schedule
    from deeplearning4j_tpu.train import Adam

    def build():
        b = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
             .list()
             .layer(DenseLayer(n_out=hidden, activation=Activation.TANH)))
        for _ in range(n_blocks):
            b = b.layer(DenseLayer(n_out=hidden, activation=Activation.TANH))
        conf = (b.layer(OutputLayer(n_out=nout, loss=LossFunction.MCXENT))
                .set_input_type(InputType.feed_forward(nin)).build())
        return MultiLayerNetwork(conf).init()

    import jax as _jax
    mesh = make_mesh(devices=_jax.devices()[:n_stages], pipe=n_stages)
    batch = 4 * n_micro
    rng = np.random.RandomState(0)
    x = rng.randn(batch, nin).astype(np.float32)
    y = np.eye(nout, dtype=np.float32)[rng.randint(0, nout, batch)]

    def timed_steps(trainer, k: int) -> float:
        _host_fence(trainer.params)
        start = time.perf_counter()
        for _ in range(k):
            trainer.fit_batch(x, y)
        _host_fence(trainer.params)
        return (time.perf_counter() - start) / k

    out = {"n_stages": n_stages, "n_micro": n_micro, "batch": batch}
    for kind in ("1f1b", "gpipe"):
        tr = PipelineParallelTrainer(build(), mesh, n_micro=n_micro,
                                     schedule=kind, stage_time_probe=False)
        timed_steps(tr, warmup_steps)
        st = tr.stats()
        out[f"bubble_share_{kind}"] = round(st["bubble_share"], 4)
        out[f"resident_microbatches_{kind}"] = st["resident_microbatches"]
        out[f"step_ms_{kind}"] = round(timed_steps(tr, bench_steps) * 1e3, 3)
        if kind == "1f1b":
            out["stage_param_bytes_per_device"] = tr.stage_param_bytes()
            out["stage_param_bytes_global"] = tr.stage_param_bytes(
                per_device=False)
    # the memory lever in one number: what M could grow to at the same
    # residency once 1F1B caps stashes at min(S, M)
    big_m = 4 * n_micro
    out["bubble_share_1f1b_4x_micro"] = round(
        build_pipeline_schedule(n_stages, big_m, "1f1b").bubble_share, 4)
    bubble = out["bubble_share_1f1b"]
    out["bubble_gate"] = {"max": bubble_gate, "value": bubble,
                          "ok": bool(bubble < bubble_gate)}
    out["note"] = (
        "bubble share is schedule-analytic ((S-1)/(M+S-1), identical for "
        "both schedules at equal M); 1F1B's win is residency — min(S, M) "
        "stashed microbatches vs GPipe's M — which is what lets M (and so "
        "the bubble denominator) grow at fixed activation memory")
    return out


_MEASUREMENTS = {
    "lenet": measure_lenet,
    "resnet50": measure_resnet50,
    "resnet50_b128": measure_resnet50_b128,
    "resnet50_e2e_fit": measure_resnet50_e2e_fit,
    "bert": measure_bert,
    "bert_b64": measure_bert_b64,
    "bert_import": measure_bert_import,
    "bert_import_train": measure_bert_import_train,
    "lstm": measure_lstm,
    "calibration": measure_calibration,
    "input_pipeline": measure_input_pipeline,
    "input_pipeline_overlap": measure_input_pipeline_overlap,
    "flash_attention_8k": measure_flash_attention_8k,
    "moe_dispatch": measure_moe_dispatch,
    "rewrite_passes": measure_rewrite_passes,
    "tracing_overhead": measure_tracing_overhead,
    "step_profile": measure_step_profile,
    "zero1_updater_headroom": measure_zero1_updater_headroom,
    "large_batch_scaling": measure_large_batch_scaling,
    "generate_decode": measure_generate_decode,
    "speculative_decode": measure_speculative_decode,
    "engine_pool_scaling": measure_engine_pool_scaling,
    "fabric_overhead": measure_fabric_overhead,
    "quantized_infer": measure_quantized_infer,
    "int8_kv_cache": measure_int8_kv_cache,
    "checkpoint_stall": measure_checkpoint_stall,
    "elastic_goodput": measure_elastic_goodput,
    "paged_kv_occupancy": measure_paged_kv_occupancy,
    "disagg_handoff": measure_disagg_handoff,
    "model_multiplex": measure_model_multiplex,
    "pipeline_bubble_share": measure_pipeline_bubble_share,
}

# extras row name -> measurement name (the artifact's "extras" keys, in
# emission order). `--rows <name,...>` selects from this table, so any
# single row — e.g. quantized_infer_speedup in CI — runs standalone.
_EXTRA_ROWS = {
    "bert": "bert",
    "bert_tf_import": "bert_import",
    "bert_tf_import_train": "bert_import_train",
    "lstm_char_rnn": "lstm",
    "lenet_smoke": "lenet",
    "calibration": "calibration",
    "input_pipeline": "input_pipeline",
    "input_pipeline_overlap": "input_pipeline_overlap",
    "resnet50_e2e_fit": "resnet50_e2e_fit",
    "rewrite_passes": "rewrite_passes",
    "tracing_overhead": "tracing_overhead",
    "step_profile": "step_profile",
    "zero1_updater_headroom": "zero1_updater_headroom",
    "large_batch_scaling": "large_batch_scaling",
    "generate_decode": "generate_decode",
    "speculative_decode": "speculative_decode",
    "engine_pool_scaling": "engine_pool_scaling",
    "fabric_overhead": "fabric_overhead",
    "quantized_infer_speedup": "quantized_infer",
    "int8_kv_cache": "int8_kv_cache",
    "checkpoint_stall": "checkpoint_stall",
    "elastic_goodput": "elastic_goodput",
    "paged_kv_occupancy": "paged_kv_occupancy",
    "disagg_handoff": "disagg_handoff",
    # weight paging beats always-warm on any platform: the >= 2x
    # registered-models-served gate runs on CPU (tiny MLPs, real
    # page-ins through the store + rewrite + warmup path)
    "model_multiplex": "model_multiplex",
    # CPU-runnable since the grouped dispatch mode: the
    # grouped_no_regression_vs_sort gate holds on any platform (small
    # shapes via the cpu kwargs); the ≤1.5 overhead ratio stays a
    # chip-only target recorded inside the row
    "moe_dispatch": "moe_dispatch",
    # schedule analytics + fenced step times run fine on 8 virtual CPU
    # devices; the <0.35 bubble gate is platform-independent
    "pipeline_bubble_share": "pipeline_bubble_share",
}
# rows with no sized-down `measure <row> cpu` spelling: real widths only
_CHIP_ONLY_ROWS = {
    "resnet50_b128": "resnet50_b128",
    "bert_b64": "bert_b64",
    "flash_attention_8k": "flash_attention_8k",
}


def select_rows(spec: str) -> dict:
    """Parse a ``--rows a,b,c`` selector against the known extras rows.
    Returns {row_name: measurement_name} preserving the caller's order;
    raises ValueError naming any unknown row (the CI contract: a typo'd
    row name fails loudly instead of silently benching nothing)."""
    known = {**_EXTRA_ROWS, **_CHIP_ONLY_ROWS}
    names = [s.strip() for s in spec.split(",") if s.strip()]
    if not names:
        raise ValueError("--rows needs at least one row name")
    unknown = [n for n in names if n not in known]
    if unknown:
        raise ValueError(
            f"unknown bench row(s) {unknown}; known rows: {sorted(known)}")
    return {n: known[n] for n in names}


# --------------------------------------------------------------------------
# orchestration (parent process)
# --------------------------------------------------------------------------

class MeasurementFailed(RuntimeError):
    """A measurement child crashed, timed out or printed no result."""


def _run_measurement(name: str, platform: str) -> dict:
    """Run one measurement in a child process and return its JSON. A child
    that fails — a device row without a chip included — raises with the
    end of its stderr: a crashed cell never becomes data in the artifact."""
    argv = [sys.executable, os.path.abspath(__file__), "measure", name,
            platform]
    try:
        out = subprocess.run(
            argv, capture_output=True, text=True, timeout=MEASURE_TIMEOUT_S,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        raise MeasurementFailed(
            f"{name} ({platform}) timed out after {MEASURE_TIMEOUT_S}s")
    if out.returncode == 0:
        for line in out.stdout.splitlines():
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)
    raise MeasurementFailed(
        f"{name} ({platform}) rc={out.returncode}: "
        + (out.stderr or "no JSON on stdout").strip()[-2000:])


def _child_measure(name: str, platform: str) -> None:
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    from deeplearning4j_tpu.core.env import enable_compile_cache

    enable_compile_cache()
    import jax

    # (the CPU spelling must not start a backend yet: some rows still
    # have to force a virtual device count through XLA_FLAGS)
    if platform != "cpu":
        found = jax.devices()[0].platform
        if found != "tpu":
            raise SystemExit(
                f"bench.py measure {name}: no TPU — JAX reports {found!r}; "
                f"`measure {name} cpu` is the CPU spelling")
    kwargs = {}
    if platform == "cpu":
        # Host CPU baseline (this box: ONE core, ~50 GFLOP/s): shrink batch
        # + iters so the denominator finishes inside the timeout, and use
        # f32 (CPUs emulate bf16). Throughput normalizes per sample/token.
        kwargs = {
            "resnet50": {"batch": 8, "warmup_iters": 1, "bench_iters": 2,
                         "compute_dtype": "float32"},
            "bert": {"batch": 2, "warmup_iters": 1, "bench_iters": 2,
                     "compute_dtype": "float32"},
            "lenet": {"warmup_iters": 8, "bench_iters": 8},
            "bert_import": {"batch": 2, "seq": 32, "warmup_iters": 1,
                            "bench_iters": 2, "hidden": 128, "layers": 2,
                            "heads": 2, "vocab": 2000},
            "bert_import_train": {"batch": 2, "seq": 16, "bench_iters": 2,
                                  "hidden": 64, "layers": 2, "heads": 2,
                                  "vocab": 500},
            "calibration": {"tiny": True},
            "input_pipeline": {"n_images": 64},
            "input_pipeline_overlap": {"n_images": 64, "raw": 64,
                                       "batch": 16, "compute_iters": 4},
            "lstm": {"batch": 4, "seq": 50, "warmup_iters": 1,
                     "bench_iters": 2},
            "resnet50_e2e_fit": {"batch": 8, "n_images": 32, "raw": 64,
                                 "out": 56, "bench_steps": 3},
            "moe_dispatch": {"tokens": 256, "d": 64, "hidden": 128,
                             "iters": 2},
            "rewrite_passes": {"batch": 4, "height": 64, "width": 64,
                               "classes": 10, "warmup_iters": 1,
                               "bench_iters": 2, "infer_iters": 3,
                               "compute_dtype": "float32"},
            "tracing_overhead": {"n_requests": 80, "warmup": 15,
                                 "repeats": 4},
            "step_profile": {"batch": 8, "n_images": 32, "raw": 64,
                             "out": 56, "bench_steps": 4, "synth_steps": 3,
                             "sync_every": 2},
            # 8 virtual devices so the sharding is real on the 1-core
            # host; shrink the model so the 8-way jits fit the timeout
            "zero1_updater_headroom": {"force_devices": 8, "nin": 64,
                                       "hidden": 256, "nout": 64,
                                       "batch_per_shard": 4,
                                       "bench_steps": 4},
            # 8 virtual devices so DP=8 grouping/bucketing is real on the
            # 1-core host; the trajectory gate needs the full step count
            "large_batch_scaling": {"force_devices": 8, "bench_steps": 4},
            # interpret-mode Pallas is slow on CPU: tiny model + short
            # cache keep the flash-vs-ref column inside the timeout
            "generate_decode": {"vocab": 64, "hidden": 64, "layers": 2,
                                "heads": 4, "max_len": 64, "batch": 4,
                                "prompt_len": 8, "decode_steps": 12,
                                "warmup_steps": 2, "attn_len": 32},
            # compute-heavy target + tiny draft: dispatch overhead must
            # not dominate the verify pass or the CPU row understates
            # the accepted-tokens/step win (defaults tuned for the
            # 1-core host; acceptance comes from the successor task)
            "speculative_decode": {"spec_steps": 12,
                                   "target_train_steps": 100,
                                   "draft_train_steps": 350},
            # 1-core host: keep the RPS passes short; scaling is reported
            # but only meaningful with >= N cores (see the row's note)
            "engine_pool_scaling": {"n_requests": 120, "threads": 4,
                                    "replicas": 2, "overload_requests": 80},
            # both legs ride real HTTP: keep the passes short, the 1-core
            # host serializes client + server threads anyway
            "fabric_overhead": {"n_requests": 80, "threads": 4},
            # the accuracy gate is the point on CPU (no int8 matmul
            # unit); keep the MLP + holdout small
            "quantized_infer": {"hidden": 128, "train_steps": 40,
                                "infer_iters": 8, "holdout": 256},
            # head_dim 64 keeps the >= 1.8x fp16-relative residency gate
            # honest; short generations fit the timeout
            "int8_kv_cache": {"hidden": 256, "heads": 4, "layers": 2,
                              "max_len": 64, "batch": 2,
                              "gen_tokens": 24, "train_steps": 50},
            # stall contrast needs a serialization cost worth hiding:
            # keep hidden wide enough that the zip write dominates the
            # device fetch, few steps so the row stays fast
            "checkpoint_stall": {"hidden": 384, "steps": 10},
            # 1-core host: longer pace amortizes the ~2-4s restore+jit
            # boot cost of each restart so the >0.90 gate reflects the
            # supervisor's bookkeeping, not this box's compile speed
            "elastic_goodput": {"total_iters": 280, "pace_s": 0.3},
            # 8 virtual devices make the pipe=4 mesh real on the 1-core
            # host; tiny blocks keep both schedule jits in the timeout
            "pipeline_bubble_share": {"force_devices": 8, "hidden": 32,
                                      "bench_steps": 2},
        }.get(name, {})
    result = _MEASUREMENTS[name](**kwargs)
    dev = jax.devices()[0]
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices())}
    print(json.dumps(result))


def _parse_rows_arg(argv):
    """``--rows a,b`` / ``--rows=a,b`` -> the spec string, else None."""
    for i, a in enumerate(argv):
        if a == "--rows":
            if i + 1 >= len(argv):
                raise ValueError("--rows needs a comma-separated row list")
            return argv[i + 1]
        if a.startswith("--rows="):
            return a.split("=", 1)[1]
    return None


def _run_selected_rows(selected: dict) -> None:
    """``--rows`` mode: run ONLY the named extras rows on the chip, print
    one JSON line keyed by row name — the standalone-row entry point
    (e.g. ``python bench.py --rows quantized_infer_speedup``)."""
    rows = {row: _run_measurement(meas, "tpu")
            for row, meas in selected.items()}
    print(json.dumps({
        "metric": f"bench rows: {', '.join(selected)}",
        "device": next(iter(rows.values()))["device"],
        "rows": rows,
    }))


def main() -> None:
    if len(sys.argv) > 2 and sys.argv[1] == "measure":
        _child_measure(sys.argv[2], sys.argv[3] if len(sys.argv) > 3
                       else "tpu")
        return
    if "--list-rows" in sys.argv[1:]:
        print(json.dumps({"rows": sorted(_EXTRA_ROWS),
                          "chip_only_rows": sorted(_CHIP_ONLY_ROWS)}))
        return
    try:
        rows_spec = _parse_rows_arg(sys.argv[1:])
        selected = select_rows(rows_spec) if rows_spec is not None else None
    except ValueError as e:
        print(json.dumps({"error": str(e)}), file=sys.stderr)
        sys.exit(2)
    if selected is not None:
        _run_selected_rows(selected)
        return

    # calibration first: cheap, validates the timer, yields the measured
    # matmul peak + conv ceiling MFU denominators — and is the child that
    # finds out whether there is a chip
    calibration = _run_measurement("calibration", "tpu")
    device = _run_measurement("resnet50", "tpu")

    extras = {}
    for row, meas in {**_EXTRA_ROWS, **_CHIP_ONLY_ROWS}.items():
        # calibration already ran (it feeds the MFU denominators)
        extras[row] = (calibration if row == "calibration"
                       else _run_measurement(meas, "tpu"))

    # input-bound vs compute-bound (VERDICT r4 ask 2): compare each host
    # pipeline mode and the e2e-from-files fit against the device step rate
    ipl = extras["input_pipeline"]
    dev_rate = (extras.get("resnet50_b128") or device).get("samples_per_sec") \
        or device.get("samples_per_sec")
    if dev_rate:
        for mode in ("float32_host_augment", "uint8_host_augment",
                     "uint8_passthrough"):
            row = ipl.get(mode)
            if isinstance(row, dict) and row.get("images_per_sec"):
                row["vs_device_step"] = round(
                    row["images_per_sec"] / dev_rate, 2)
        e2e = extras.get("resnet50_e2e_fit", {})
        if e2e.get("samples_per_sec"):
            e2e["vs_synthetic_step"] = round(
                e2e["samples_per_sec"] / dev_rate, 4)

    measured_peak = calibration.get("measured_peak_tflops")
    conv_ceiling = calibration.get("conv_ceiling_tflops")
    for row in (device, extras["bert"], extras.get("resnet50_b128", {}),
                extras.get("bert_b64", {})):
        if row.get("model_tflops_per_sec") and measured_peak:
            row["mfu_vs_measured_peak"] = round(
                row["model_tflops_per_sec"] / measured_peak, 4)
    for row in (device, extras.get("resnet50_b128", {})):
        if row.get("model_tflops_per_sec") and conv_ceiling:
            row["mfu_vs_conv_ceiling"] = round(
                row["model_tflops_per_sec"] / conv_ceiling, 4)

    # timer self-checks on MEDIANS (VERDICT r4 ask 3)
    suspect = []
    for label, row in (("resnet50", device), ("bert", extras["bert"]),
                       ("resnet50_b128", extras.get("resnet50_b128", {})),
                       ("bert_b64", extras.get("bert_b64", {}))):
        if row.get("mfu") and row["mfu"] > 0.9:
            suspect.append(f"{label} mfu={row['mfu']:.3f} > 0.9")
    for label, row in (("resnet50", device),
                       ("resnet50_b128", extras.get("resnet50_b128", {}))):
        if row.get("mfu_vs_conv_ceiling") and row["mfu_vs_conv_ceiling"] > 1.0:
            suspect.append(
                f"{label} above conv ceiling "
                f"({row['mfu_vs_conv_ceiling']:.2f}) — calibration broken")
    if calibration.get("timer_disagreement") \
            and calibration["timer_disagreement"] > 2.0:
        suspect.append(
            f"block_until_ready vs host-fence disagree "
            f"{calibration['timer_disagreement']}x on calibration matmul "
            "(fence timing is authoritative)")

    value = device.get("samples_per_sec")
    vs_baseline = None
    baseline_config = None
    cpu_base = _run_measurement("resnet50", "cpu")
    base = cpu_base.get("samples_per_sec")
    if value and base:
        vs_baseline = round(value / base, 2)
        baseline_config = {
            "platform": "cpu", "batch": cpu_base.get("batch"),
            "compute_dtype": cpu_base.get("compute_dtype"),
            "samples_per_sec": round(base, 2),
            "note": "per-sample throughput ratio across configs "
                    "(device batch/dtype differ; see metric string)",
        }

    result = {
        "metric": "ResNet-50 synthetic-ImageNet train samples/sec/chip "
                  f"(ComputationGraph.fit, batch={device.get('batch')}, "
                  f"{device.get('compute_dtype', 'f32')})",
        "value": round(value, 2) if value else None,
        "unit": "samples/sec",
        "vs_baseline": vs_baseline,
        "baseline_config": baseline_config,
        "device": device["device"],
        "mfu": round(device["mfu"], 4) if device.get("mfu") else None,
        "mfu_vs_measured_peak": device.get("mfu_vs_measured_peak"),
        "mfu_vs_conv_ceiling": device.get("mfu_vs_conv_ceiling"),
        "timing_method": "host-fence (D2H fetch of a result-dependent "
                         f"scalar); every row = median of {REPEATS} with "
                         "spread",
        "extras": extras,
    }
    if suspect:
        result["timing_suspect"] = any(
            "mfu" in s or "ceiling" in s for s in suspect)
        result["timing_notes"] = suspect
    print(json.dumps(result))


if __name__ == "__main__":
    main()
