"""Where the served LFM2 leaves the benchmark's plain reference, and whether
a fault or the precision does it (PERF.md section 2, PR 36).

    python tools/lfm2_diag.py [--f32] [--tiny]      # from the repo's root

The first six layers of ``benchmarks/configs/lfm2-8b-a1b-pp2.json`` at the
published widths (``--tiny``: toy widths, for the CPU), the seed's weights,
one sequence of 1,024 ids: ``output()`` over the whole sequence, then a
right-padded prefill of 700 tokens and 48 cached decode steps, each against
the family's reference at the same positions. In bfloat16 (the default) the
routing flips against the float32 reference and the logits differ by tenths;
with ``--f32`` (float32 at "highest") the two agree to rounding: a difference
there is a fault of the program's (kernels, buckets, dispatch), not the
precision's. Prints, never asserts; one process, about 3 minutes on the chip.
"""
import json, os, sys
sys.path.insert(0, os.getcwd())
import numpy as np
import jax, jax.numpy as jnp
from benchmarks.harness import runtime, weights
from deeplearning4j_tpu.model.zoo import Lfm2MoeLM
from deeplearning4j_tpu.nn.sequential import MultiLayerNetwork
from deeplearning4j_tpu.generate.session import GenerationSession
from deeplearning4j_tpu.ops import set_attention_impl
from deeplearning4j_tpu.nn.layers.moe import ExpertShareMoELayer

TINY = "--tiny" in sys.argv
cfg = json.load(open("benchmarks/configs/lfm2-8b-a1b-pp2.json"))
types = cfg["model"]["layer_types"][:6]
m = dict(cfg["model"], layer_types=types)
dtype = "float32" if "--f32" in sys.argv else "bfloat16"
if dtype == "float32":
    jax.config.update("jax_default_matmul_precision", "highest")
T, N = 1024, 700
if TINY:
    m.update(vocab_size=512, hidden=64, n_heads=4, n_kv_heads=2, ffn_size=128, expert_ffn_size=32, n_experts=8, top_k=2)
    T, N = 256, 150
fam = runtime.load_family("benchmarks/families/lfm2_moe.py")
d = fam.dims({"model": m})
seed = 4600000001
model = MultiLayerNetwork(Lfm2MoeLM(**m, seed=1, dtype=dtype).conf())
weights.install(model, weights.program_weights(fam, d, seed, dtype, cfg["layout"]))
w = weights.make_weights(fam, d, seed, dtype)
ids = np.random.default_rng(0).integers(0, d["vocab_size"], (1, T))
ref = np.asarray(jax.jit(lambda w, i: fam.decoder_logits(w, i, d))(w, jnp.asarray(ids)))[0]  # [T, V]
print("ref logits std", ref.std(), flush=True)

def report(name, got, at):
    r = ref[at]
    diff = np.abs(got - r)
    pick = got.argmax(-1)
    gap = r.max(-1) - np.take_along_axis(r, pick[:, None], -1)[:, 0]
    print(f"{name:34s} mean|d| {diff.mean():.5f} max|d| {diff.max():.4f} "
          f"off_best {np.mean(pick != r.argmax(-1)):.3f} mean_gap {gap.mean():.5f} "
          f"first64 {diff[:64].mean():.5f} last64 {diff[-64:].mean():.5f}", flush=True)

def whole(name):
    model._output_fn_cache.clear()
    out = np.asarray(model.output(jnp.asarray(ids)), np.float32)[0].T  # [T, V]
    report(name, out, np.arange(T))

def cached(name):
    sess = GenerationSession(model, max_len=2048 if not TINY else 512)
    carry, logits, _ = sess.prefill([ids[0, :N].tolist()])
    got = [np.asarray(logits, np.float32)[0]]
    for i in range(N, N + 48):
        carry, lg = sess.decode(carry, [int(ids[0, i])])
        got.append(np.asarray(lg, np.float32)[0])
    got = np.stack(got)
    report(name + " prefill", got[:1], np.asarray([N - 1]))
    report(name + " steps", got[1:], np.arange(N, N + 48))

for impl in (("auto",) if dtype == "float32" else ("auto", "xla")):
    set_attention_impl(impl)
    whole(f"whole [{impl}]")
    cached(f"cached [{impl}]")
set_attention_impl("auto")
