"""``loop_tolerance.py`` for the SambaY decoder.  Measure what the reference
check of the cell is set from: the spread between the system's forward in its
compute dtype and the plain float32 reference, over seeds, at the
configuration's published widths on ONE chip (no mesh).

    python benchmark/rehearse/sambay_tolerance.py <config.json> <seeds> [--rehearse] [forms]

Per seed: seeded weights, three antithetic pairs' noise from the table, both
signs of one pair and two more members; for each the fitness (mean log p of
the next token) and the behaviour logits (the last position's, at 32 ids), by
(a) the system's perturbed forward in bfloat16, (b) every projection's input
rounded to fp8, (c) the rank-r correction left out of one projection, (d) λ
set to 0, (e) the window ignored, (f) m taken from the first Mamba layer, (g)
the cross layer given its own keys, (h) the system in float32, each against
the reference.  Prints one line per member and a summary: the largest honest
difference and the smallest degraded one.  ``forms``: a comma-separated
choice of those names (all of them where left out).
"""

import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import coarse_sambay
    from benchmark.files import load_file_module
    from estorch_tpu.models import SambaYLM
    from estorch_tpu.ops.lowrank import make_lowrank_tree_spec

    config = json.load(open(sys.argv[1]))
    seeds = int(sys.argv[2])
    rest = [a for a in sys.argv[3:] if a != "--rehearse"]
    if "--rehearse" in sys.argv:
        config["build"]["kwargs"].update(config["rehearsal_kwargs"])
    ref = load_file_module(os.path.join(ROOT, "benchmark", "reference",
                                        config["reference"] + ".py"))
    s = ref.sizes(config)
    kwargs = config["build"]["kwargs"]["policy_kwargs"]
    table = jax.random.normal(jax.random.key(0), (1 << 25,), jnp.float32)
    sigma = config["build"]["kwargs"]["sigma"]
    forms = {"bf16": (SambaYLM, jnp.bfloat16),
             "fp8_inputs": (coarse_sambay.Fp8SambaY, jnp.bfloat16),
             "dropped_correction": (coarse_sambay.DroppedCorrectionSambaY,
                                    jnp.bfloat16),
             "lambda_zero": (coarse_sambay.PlainAttentionSambaY,
                             jnp.bfloat16),
             "no_window": (coarse_sambay.NoWindowSambaY, jnp.bfloat16),
             "first_memory": (coarse_sambay.FirstMemorySambaY, jnp.bfloat16),
             "own_keys": (coarse_sambay.OwnKeysCrossSambaY, jnp.bfloat16),
             "f32": (SambaYLM, jnp.float32)}
    if rest:
        forms = {name: forms[name] for name in rest[0].split(",")}
    print(f"device {jax.devices()[0].device_kind}; sizes {ref.describe(config)}")
    lm = SambaYLM(**kwargs)
    shapes = lm.param_shapes()
    spec = make_lowrank_tree_spec(shapes, s["low_rank"],
                                  dense=lm.dense_noise_leaves)
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    paths = ["/".join(str(k.key) for k in p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    keep_f32 = set(lm.float32_leaves)
    ids = jnp.asarray(ref.probe_ids(s, 32))
    log_v = math.log(s["vocab_size"])
    floor = config["reference_tolerance"]["fitness_floor"]

    def unravel(flat, dtype):
        # the copy the engine's forward reads: the compute dtype, what the
        # decay and the differential lambda are made of float32.  Cut on
        # the host, a leaf at a time: inside one program XLA lays the whole
        # flat vector out 16 wide for ``A_log``'s sake and pads it to 22 GB
        out, at = [], 0
        for leaf, path in zip(leaves, paths):
            n = math.prod(leaf.shape)
            out.append(jnp.asarray(flat[at:at + n].reshape(leaf.shape)).astype(
                jnp.float32 if path in keep_f32 else dtype))
            at += n
        return jax.tree_util.tree_unflatten(treedef, out)

    programs = {}
    for name, (cls, dtype) in forms.items():
        model = cls(**kwargs)

        def program(params, noise, c, tokens, model=model):
            logp, last = model.perturbed_apply(
                params, spec.unpack(noise), c, tokens)
            return jnp.mean(logp), jnp.take(last, ids)

        programs[name] = (jax.jit(program), dtype)

    worst = {name: {"fit_rel": [], "bc": []} for name in forms}
    corpus = ref.corpus(s)
    for seed in range(seeds):
        t = time.perf_counter()
        key = jax.random.PRNGKey(1_000_003 * (seed + 1))
        theta = np.asarray(ref.init_theta(jax.random.fold_in(key, 0),
                                          config))
        trees = {dtype: unravel(theta, dtype)
                 for dtype in {d for _, d in programs.values()}}
        offs = np.asarray(jax.random.randint(
            jax.random.fold_in(key, 1), (3,), 0,
            table.shape[0] - spec.noise_dim))
        rows = np.asarray(jax.random.randint(
            jax.random.fold_in(key, 2), (3,), 0, s["corpus_sequences"]))
        for pair, sign in [(0, 1.0), (0, -1.0), (1, 1.0), (2, -1.0)]:
            noise = jax.lax.dynamic_slice(table, (int(offs[pair]),),
                                          (spec.noise_dim,))
            tokens = corpus[int(rows[pair])]
            c = jnp.float32(sigma * sign)
            logp, last = ref.forward(s, ref.Member(s, theta, noise, c),
                                     tokens)
            want = float(jnp.mean(logp))
            want_bc = np.asarray(jnp.take(last, ids))
            line = [f"seed {seed} pair {pair} sign {sign:+.0f} reference "
                    f"{want:.8f} (+log V {want + log_v:.6g})"]
            for name, (fn, dtype) in programs.items():
                got, got_bc = fn(trees[dtype], noise, c, tokens)
                rel = abs(float(got) - want) / max(abs(want + log_v), floor)
                bc = float(np.abs(np.asarray(got_bc) - want_bc).max())
                worst[name]["fit_rel"].append(rel)
                worst[name]["bc"].append(bc)
                line.append(f"{name} rel {rel:.4g} bc {bc:.4g}")
            print("; ".join(line), flush=True)
        print(f"seed {seed} took {time.perf_counter() - t:.1f} s", flush=True)
    for name, w in worst.items():
        print(f"SUMMARY {name}: fitness relative difference median "
              f"{np.median(w['fit_rel']):.4g} max {max(w['fit_rel']):.4g} "
              f"min {min(w['fit_rel']):.4g}; behaviour difference median "
              f"{np.median(w['bc']):.4g} max {max(w['bc']):.4g} min "
              f"{min(w['bc']):.4g}")


if __name__ == "__main__":
    main()
