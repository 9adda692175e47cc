"""``loop_tolerance.py`` for the sparse-expert model.  Measure what the
reference check of the cell is set from: the spread between the system's
forward in its compute dtype and the plain float32 reference, over seeds, at
the configuration's published widths on ONE chip (no mesh), and the share of
routes that differ between the two.

    python benchmark/rehearse/moe_tolerance.py <config.json> <seeds> [--rehearse]

Per seed: seeded weights, one antithetic pair's noise from the table, both
signs and two more members; for each the fitness (mean of main_t + lambda
mtp_t) and the behaviour logits, by (a) the system's perturbed forward in
bfloat16, (b) every projection's and every expert's input rounded to fp8,
(c) the router's matmul in bfloat16, (d) the rank-r correction left out of
one stacked expert leaf, (e) the MTP term left out, (f) the selection bias
added to the weights, (g) the system in float32, each against the reference.
For (a), (c) and (g) also the share of (token, expert layer) routes whose
set of 8 experts differs from the reference's, and of those that differ in
an expert HELD here.  Prints one line per member and a summary: the largest
honest difference and the smallest degraded one.
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

    import coarse_moe
    from benchmark.files import load_file_module
    from estorch_tpu.models import MoELM, lm_blocks
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
    forms = {"bf16": (MoELM, jnp.bfloat16),
             "fp8_inputs": (coarse_moe.Fp8Moe, jnp.bfloat16),
             "bf16_router": (coarse_moe.Bf16RouterMoe, jnp.bfloat16),
             "dropped_expert_correction": (
                 coarse_moe.DroppedExpertCorrectionMoe, jnp.bfloat16),
             "no_mtp": (coarse_moe.NoMtpMoe, jnp.bfloat16),
             "bias_in_weights": (coarse_moe.BiasInWeightsMoe, jnp.bfloat16),
             "f32": (MoELM, jnp.float32)}
    print(f"device {jax.devices()[0].device_kind}; sizes {ref.describe(config)}")
    lm = MoELM(**kwargs)
    shapes = lm.param_shapes()
    spec = make_lowrank_tree_spec(shapes, s["low_rank"],
                                  stacked=lm.stacked_leaves)
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    paths = ["/".join(str(k.key) for k in p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    keep_f32 = set(lm.float32_leaves)
    ids = jnp.asarray(ref.probe_ids(s, 32))
    log_v = math.log(s["vocab_size"])
    floor = config["reference_tolerance"]["fitness_floor"]
    first, held = s["first_held"], s["n_routed_experts"]

    def unravel(flat, dtype):
        # the copy the engine's forward reads: the compute dtype, the
        # routers and their biases float32
        out, at = [], 0
        for leaf, path in zip(leaves, paths):
            n = math.prod(leaf.shape)
            out.append(flat[at:at + n].reshape(leaf.shape).astype(
                jnp.float32 if path in keep_f32 else dtype))
            at += n
        return jax.tree_util.tree_unflatten(treedef, out)

    programs = {}
    for name, (cls, dtype) in forms.items():
        model = cls(**kwargs)

        def program(theta, noise, c, tokens, model=model, dtype=dtype):
            # the routes the forward takes, kept as it is traced
            chosen = []

            def recording(honest):
                def routed_experts(p, noise, c, u, experts, *a, **kw):
                    chosen.append(experts)
                    return honest(p, noise, c, u, experts, *a, **kw)
                return routed_experts

            with coarse_moe.standing_in("routed_experts", recording):
                logp, last, _ = model.perturbed_apply(
                    unravel(theta, dtype), spec.unpack(noise), c, tokens)
            return jnp.mean(logp), jnp.take(last, ids), jnp.stack(chosen)

        programs[name] = jax.jit(program)

    worst = {name: {"fit_rel": [], "bc": [], "routes": [], "held": []}
             for name in forms}
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
            main, mtp, last, routes = ref.heads(
                s, ref.Member(s, theta, noise, c), tokens, with_routes=True)
            want = float(jnp.mean(main + s["mtp_lambda"] * mtp))
            want_bc = np.asarray(jnp.take(last, ids))
            want_routes = np.sort(np.stack([np.asarray(r) for r in routes]),
                                  axis=-1)
            line = [f"seed {seed} pair {pair} sign {sign:+.0f} reference "
                    f"{want:.8f} (+log V {want + log_v:.6g})"]
            for name, fn in programs.items():
                got, got_bc, got_routes = fn(theta, noise, c, tokens)
                rel = abs(float(got) - want) / max(abs(want + log_v), floor)
                bc = float(np.abs(np.asarray(got_bc) - want_bc).max())
                got_routes = np.sort(np.asarray(got_routes), axis=-1)
                differ = (got_routes != want_routes).any(axis=-1)
                here = lambda r: np.where(  # noqa: E731
                    (r >= first) & (r < first + held), r, -1)
                differ_held = (np.sort(here(got_routes), axis=-1)
                               != np.sort(here(want_routes), axis=-1)
                               ).any(axis=-1)
                worst[name]["fit_rel"].append(rel)
                worst[name]["bc"].append(bc)
                worst[name]["routes"].append(float(differ.mean()))
                worst[name]["held"].append(float(differ_held.mean()))
                line.append(f"{name} rel {rel:.4g} bc {bc:.4g} routes "
                            f"{differ.mean():.4g} held {differ_held.mean():.4g}")
            print("; ".join(line), flush=True)
        print(f"seed {seed} took {time.perf_counter() - t:.1f} s", flush=True)
    for name, w in worst.items():
        print(f"SUMMARY {name}: fitness relative difference median "
              f"{np.median(w['fit_rel']):.4g} max {max(w['fit_rel']):.4g} "
              f"min {min(w['fit_rel']):.4g}; behaviour difference median "
              f"{np.median(w['bc']):.4g} max {max(w['bc']):.4g} min "
              f"{min(w['bc']):.4g}; share of (token, layer) routes that "
              f"differ from the reference's median {np.median(w['routes']):.4g} "
              f"max {max(w['routes']):.4g}, in a held expert median "
              f"{np.median(w['held']):.4g} max {max(w['held']):.4g}")


if __name__ == "__main__":
    main()
