"""``lm_tolerance.py`` for the looped model (that script builds ``HybridLM`` and
its degraded forms by name and cannot take this configuration).  Measure what
the reference check of the looped-model cell is set from:
the spread between the system's forward in its compute dtype and the plain
float32 reference, over seeds, at the configuration's published widths on
ONE chip (no mesh: the sharded program differs from this by the order of
float32 partial sums only).

    python benchmark/rehearse/loop_tolerance.py <config.json> <seeds> [--rehearse]

Per seed: seeded weights, one antithetic pair's noise from the table, both
signs and two more members; for each the fitness (mean exit-weighted
next-token log p) and the behaviour logits, by (a) the system's perturbed
forward in bfloat16, (b) the same with every projection's input rounded to
fp8, (c) the same with the rank-r correction left out of one leaf (four
uses), (d) the score read from the last pass alone, (e) the system in
float32, each against the reference.  Prints one line per member and a
summary: the largest honest difference and the smallest degraded one.
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

    import coarse_loop
    from benchmark.files import load_file_module
    from estorch_tpu.models import LoopedLM
    from estorch_tpu.ops.lowrank import make_lowrank_tree_spec

    config = json.load(open(sys.argv[1]))
    seeds = int(sys.argv[2])
    if "--rehearse" in sys.argv:
        config["build"]["kwargs"].update(config["rehearsal_kwargs"])
    ref = load_file_module(os.path.join(ROOT, "benchmark", "reference",
                                        config["reference"] + ".py"))
    s = ref.sizes(config)
    kwargs = config["build"]["kwargs"]["policy_kwargs"]
    table = jax.random.normal(jax.random.key(0), (1 << 25,), jnp.float32)
    sigma = config["build"]["kwargs"]["sigma"]
    forms = {"bf16": (LoopedLM, jnp.bfloat16),
             "fp8_activations": (coarse_loop.Fp8Loop, jnp.bfloat16),
             "dropped_correction": (coarse_loop.DroppedCorrectionLoop,
                                    jnp.bfloat16),
             "last_pass_score": (coarse_loop.LastPassScoreLoop,
                                 jnp.bfloat16),
             "f32": (LoopedLM, jnp.float32)}
    print(f"device {jax.devices()[0].device_kind}; sizes {ref.describe(config)}")
    lm = LoopedLM(**kwargs)
    shapes = lm.param_shapes()
    spec = make_lowrank_tree_spec(shapes, s["low_rank"])
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    ids = jnp.asarray(ref.probe_ids(s, 32))
    log_v = math.log(s["vocab_size"])
    # relative to |fitness + log vocabulary| floored as the runner floors it
    floor = config["reference_tolerance"]["fitness_floor"]

    def unravel(flat, dtype):
        out, at = [], 0
        for leaf in leaves:
            n = math.prod(leaf.shape)
            out.append(flat[at:at + n].reshape(leaf.shape).astype(dtype))
            at += n
        return jax.tree_util.tree_unflatten(treedef, out)

    programs = {}
    for name, (cls, dtype) in forms.items():
        model = cls(**kwargs)

        def program(theta, noise, c, tokens, model=model, dtype=dtype):
            logp, last = model.perturbed_apply(
                unravel(theta, dtype), spec.unpack(noise), c, tokens)
            return jnp.mean(logp), jnp.take(last, ids)

        programs[name] = jax.jit(program)

    worst = {name: {"fit_rel": [], "bc": []} for name in forms}
    corpus = ref.corpus(s)
    for seed in range(seeds):
        t = time.perf_counter()
        key = jax.random.PRNGKey(1_000_003 * (seed + 1))
        theta = ref.init_theta(jax.random.fold_in(key, 0), config)
        offs = np.asarray(jax.random.randint(
            jax.random.fold_in(key, 1), (3,), 0,
            table.shape[0] - spec.noise_dim))
        rows = np.asarray(jax.random.randint(
            jax.random.fold_in(key, 2), (3,), 0, s["corpus_sequences"]))
        members = [(0, 1.0), (0, -1.0), (1, 1.0), (2, -1.0)]
        for pair, sign in members:
            noise = jax.lax.dynamic_slice(table, (int(offs[pair]),),
                                          (spec.noise_dim,))
            tokens = corpus[int(rows[pair])]
            c = jnp.float32(sigma * sign)
            logp, last = ref.forward(
                s, ref.Member(s, theta, noise, c), tokens)
            want, want_bc = float(jnp.mean(logp)), np.asarray(
                jnp.take(last, ids))
            line = [f"seed {seed} pair {pair} sign {sign:+.0f} reference "
                    f"{want:.8f} (+log V {want + log_v:.6g})"]
            for name, fn in programs.items():
                got, got_bc = fn(theta, noise, c, tokens)
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
