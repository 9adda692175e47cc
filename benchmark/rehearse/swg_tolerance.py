"""``moe_tolerance.py`` for the decoder whose two kinds of attention layer
differ in head count, band and rotation, with a gate a head (sliding layers of
512 keys beside full ones under YaRN on half a head, sigmoid-routed experts
beside a shared one, behind one dense layer).  Measure what the reference
check of the cell is set from: the spread between the system's forward in its
compute dtype and the plain float32 reference, over seeds, at the
configuration's published widths on ONE chip (no mesh).

    python benchmark/rehearse/swg_tolerance.py <config.json> <seeds> [--rehearse] [--xla] [--std=name=value,...] [forms]

Per seed: seeded weights, three antithetic pairs' noise from the table, both
signs of one pair and two more members; for each the fitness (mean log p of
the next token) and the behaviour vector (the head's logits averaged over the
last positions, at 32 ids), by (a) the system's perturbed forward in
bfloat16, (b) every projection's and expert's input rounded to fp8, (c) the
residual stream, the norms and the router in bfloat16 as well, (d) the gate
left out, (e) the full layers rotated over the whole head and (f) under plain
rope, (g) the band dropped and (h) widened by a block, (i) the full layers'
grouping in the sliding layers, (j) a softmax router, (k) the 2.5 left out,
(l) the shared expert left out, (m) the held experts of another rank, (n) the
system in float32, each against the reference.  For (a) and (n) also the share
of the system's (token, layer) routes that differ from the reference's, in
any expert and in a HELD one, and the held experts' share of the pairs with
the fullest held expert over their mean.  Prints one line per member and a
summary: the largest honest difference and the smallest degraded one.
``forms``: a comma-separated choice of those names (all where left out).
``--std=o=0.008,experts/down=0.02``: ``seeded_std`` entries tried without
editing the file.

On a TPU the forwards are traced inside the attention kernel's scope, as the
engine of the cell traces them on one chip (the forms the cell runs: the
kernel in the full layers, the XLA form under the band, the head's and the
combine's kernels); ``--xla`` or any other backend takes the XLA forms.
"""

import contextlib
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

TAPS = {"routes": []}


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import coarse_swg
    from benchmark.files import load_file_module
    from estorch_tpu.models import GatedWindowMoELM, lm_blocks
    from estorch_tpu.ops.lowrank import make_lowrank_tree_spec
    from estorch_tpu.ops.pallas_attention import kernel_scope

    config = json.load(open(sys.argv[1]))
    seeds = int(sys.argv[2])
    rest = [a for a in sys.argv[3:] if not a.startswith("--")]
    if "--rehearse" in sys.argv:
        config["build"]["kwargs"].update(config["rehearsal_kwargs"])
        config["seeded_std"] = config.get("rehearsal_seeded_std",
                                          config["seeded_std"])
    for arg in sys.argv[3:]:
        if arg.startswith("--std="):
            config["seeded_std"].update(
                (k, float(v)) for k, v in (
                    kv.split("=") for kv in arg[len("--std="):].split(",")))
    ref = load_file_module(os.path.join(ROOT, "benchmark", "reference",
                                        config["reference"] + ".py"))
    s = ref.sizes(config)
    kwargs = config["build"]["kwargs"]["policy_kwargs"]
    on_tpu = jax.devices()[0].platform == "tpu" and "--xla" not in sys.argv
    scope = ((lambda: kernel_scope(False)) if on_tpu
             else contextlib.nullcontext)
    table = jax.random.normal(jax.random.key(0), (1 << 25,), jnp.float32)
    sigma = config["build"]["kwargs"]["sigma"]

    class Tapped(GatedWindowMoELM):
        """The honest model, which also hands out how it routed."""

        def _routed(self, moe, noise, c, b, dtype):
            experts, _ = lm_blocks.route(
                moe, noise, c, b, top_k=self.num_experts_per_tok,
                scaling=self.moe_routed_scaling_factor)
            TAPS["routes"].append(experts)
            return GatedWindowMoELM._routed(self, moe, noise, c, b, dtype)

    forms = {"bf16": (Tapped, jnp.bfloat16),
             "fp8_inputs": (coarse_swg.Fp8Swg, jnp.bfloat16),
             "all_bf16": (coarse_swg.AllBf16Swg, jnp.bfloat16),
             "no_gate": (coarse_swg.NoGateSwg, jnp.bfloat16),
             "whole_head_rotation": (coarse_swg.WholeHeadRotationSwg,
                                     jnp.bfloat16),
             "plain_rope": (coarse_swg.PlainRopeSwg, jnp.bfloat16),
             "no_band": (coarse_swg.NoBandSwg, jnp.bfloat16),
             "wider_band": (coarse_swg.WiderBandSwg, jnp.bfloat16),
             "full_grouping": (coarse_swg.FullGroupingSwg, jnp.bfloat16),
             "softmax_router": (coarse_swg.SoftmaxRouterSwg, jnp.bfloat16),
             "unscaled_router": (coarse_swg.UnscaledRouterSwg, jnp.bfloat16),
             "no_shared": (coarse_swg.NoSharedSwg, jnp.bfloat16),
             "other_rank": (coarse_swg.OtherRankSwg, jnp.bfloat16),
             "f32": (Tapped, jnp.float32)}
    if rest:
        forms = {name: forms[name] for name in rest[0].split(",")}
    print(f"device {jax.devices()[0].device_kind}; the forms the cell runs "
          f"(kernel scope): {on_tpu}; seeded_std {config['seeded_std']}; "
          f"sizes {ref.describe(config)}")
    lm = GatedWindowMoELM(**kwargs)
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
    first, held = s["first_held"], s["num_experts"]

    def unravel(flat, dtype):
        # the copy the engine's forward reads: the compute dtype, float32
        # for the leaves that decide a discrete choice
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
            TAPS["routes"] = []
            with scope():
                out = model.perturbed_apply(params, spec.unpack(noise), c,
                                            tokens)
            return (jnp.mean(out[0]), jnp.take(out[1], ids), out[2],
                    list(TAPS["routes"]))

        programs[name] = (jax.jit(program), dtype)

    @jax.jit
    def differing(got_routes, want_routes):
        """((token, layer) routes that differ, those that differ in a held
        expert, routes)."""
        any_, held_, n = 0, 0, 0
        for g, w in zip(got_routes, want_routes):
            g, w = jnp.sort(g, axis=-1), jnp.sort(w, axis=-1)
            any_ = any_ + jnp.sum(jnp.any(g != w, axis=-1))

            def here(x):
                return jnp.sort(jnp.where((x >= first) & (x < first + held),
                                          x, -1), axis=-1)
            held_ = held_ + jnp.sum(jnp.any(here(g) != here(w), axis=-1))
            n += g.shape[0]
        return any_, held_, n

    worst = {name: {"fit_rel": [], "bc": [], "route": [], "held": [],
                    "share": [], "fullest": []} for name in forms}
    pairs_all = (s["mlp_layer_types"].count("sparse") * s["seq_len"]
                 * s["num_experts_per_tok"])
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
            t_ref = time.perf_counter()
            logp, last, want_routes = ref.forward(
                s, ref.Member(s, theta, noise, c), tokens, with_choices=True)
            want = float(jnp.mean(logp))
            want_bc = np.asarray(jnp.take(last, ids))
            line = [f"seed {seed} pair {pair} sign {sign:+.0f} reference "
                    f"{want:.8f} (+log V {want + log_v:.6g}) in "
                    f"{time.perf_counter() - t_ref:.1f} s"]
            for name, (fn, dtype) in programs.items():
                try:
                    got, got_bc, load, routes = fn(trees[dtype], noise, c,
                                                   tokens)
                except Exception as e:  # a form the backend refuses
                    line.append(f"{name} FAILED {type(e).__name__}: "
                                f"{str(e)[:200]}")
                    continue
                rel = abs(float(got) - want) / max(abs(want + log_v), floor)
                bc = float(np.abs(np.asarray(got_bc) - want_bc).max())
                worst[name]["fit_rel"].append(rel)
                worst[name]["bc"].append(bc)
                said = f"{name} rel {rel:.4g} bc {bc:.4g}"
                if routes:
                    any_, held_, n = (int(x) for x in differing(
                        routes, want_routes))
                    load = np.asarray(load)
                    worst[name]["route"].append(any_ / n)
                    worst[name]["held"].append(held_ / n)
                    worst[name]["share"].append(load.sum() / pairs_all)
                    # over the layers' sum: the fullest held expert
                    worst[name]["fullest"].append(load.max() / load.mean())
                    said += (f" routes {any_}/{n} held {held_}/{n} pairs "
                             f"held {int(load.sum())}/{pairs_all} fullest "
                             f"{load.max() / load.mean():.4f}")
                del routes
                line.append(said)
            del want_routes
            print("; ".join(line), flush=True)
        print(f"seed {seed} took {time.perf_counter() - t:.1f} s", flush=True)
    for name, w in worst.items():
        said = (f"SUMMARY {name}: fitness relative difference median "
                f"{np.median(w['fit_rel']):.4g} max {max(w['fit_rel']):.4g} "
                f"min {min(w['fit_rel']):.4g}; behaviour difference median "
                f"{np.median(w['bc']):.4g} max {max(w['bc']):.4g} min "
                f"{min(w['bc']):.4g}")
        if w["route"]:
            said += (f"; routes that differ median "
                     f"{np.median(w['route']):.4g} ({min(w['route']):.4g} to "
                     f"{max(w['route']):.4g}), in a held expert "
                     f"{np.median(w['held']):.4g} ({min(w['held']):.4g} to "
                     f"{max(w['held']):.4g}); the held experts' share of "
                     f"the pairs {min(w['share']):.4f} to "
                     f"{max(w['share']):.4f}, the fullest over their mean "
                     f"{min(w['fullest']):.4f} to {max(w['fullest']):.4f}")
        print(said)


if __name__ == "__main__":
    main()
