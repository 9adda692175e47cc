"""``dsa_tolerance.py`` for the decoder whose attention is computed inside a
compressed latent.  Measure what the reference check of the cell is set from:
the spread between the system's forward in its compute dtype and the plain
float32 reference, over seeds, at the configuration's published widths on ONE
chip (no mesh).

    python benchmark/rehearse/cca_tolerance.py <config.json> <seeds> [--rehearse] [--xla] [--std=<leaf>=<spread>,...] [forms]

Per seed: seeded weights, three antithetic pairs' noise from the table, both
signs of one pair and two more members; for each the fitness (mean log p of
the next token) and the behaviour vector (the head's logits averaged over the
last positions, at 32 ids), by (a) the system's perturbed forward in
bfloat16, (b) every projection's, the head-mixing convolution's and every
expert's input rounded to fp8, (c) the value shift left out, (d) the q-k mean
left out, (e) gamma = 0, (f) the routing weight renormalised to 1, (g) the
whole head rotated, (h) the held experts of another rank, (i) the system in
float32, each against the reference.  For (a) and (i) also the share of the
system's (token, layer) routes that differ from the reference's, overall and
where either side chose a HELD expert.  Prints one line per member and a
summary: the largest honest difference and the smallest degraded one.
``forms``: a comma-separated choice of those names (all of them where left
out).

``--std=down=0.01,o=0.03`` lays other spreads over the file's ``seeded_std``:
what the two readings would be under other seeded weights, before the file
is changed.

On a TPU the forwards are traced inside the attention kernel's scope, as the
engine of the cell traces them on one chip (the forms the cell runs: the
attention kernel, the tied head's kernel); ``--xla`` or any other backend
takes the XLA forms.
"""

import contextlib
import dataclasses
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

    import coarse_cca
    from benchmark.files import load_file_module
    from estorch_tpu.models import CCAMoELM
    from estorch_tpu.models.perturbed import lowrank_spec_for
    from estorch_tpu.ops.pallas_attention import kernel_scope

    config = json.load(open(sys.argv[1]))
    seeds = int(sys.argv[2])
    rest = [a for a in sys.argv[3:] if not a.startswith("--")]
    if "--rehearse" in sys.argv:
        config["build"]["kwargs"].update(config["rehearsal_kwargs"])
    for arg in sys.argv:
        if arg.startswith("--std="):
            config["seeded_std"].update(
                (name, float(value)) for name, value in
                (pair.split("=") for pair in arg[len("--std="):].split(",")))
    ref = load_file_module(os.path.join(ROOT, "benchmark", "reference",
                                        config["reference"] + ".py"))
    s = ref.sizes(config)
    kwargs = config["build"]["kwargs"]["policy_kwargs"]
    on_tpu = jax.devices()[0].platform == "tpu" and "--xla" not in sys.argv
    scope = ((lambda: kernel_scope(False)) if on_tpu
             else contextlib.nullcontext)
    table = jax.random.normal(jax.random.key(0), (1 << 25,), jnp.float32)
    sigma = config["build"]["kwargs"]["sigma"]

    @dataclasses.dataclass(frozen=True)
    class Tapped(CCAMoELM):
        """The honest model, which also hands out how it routed."""

        def perturbed_apply(self, *a):
            def tapped(honest):
                def route(*args, **kw):
                    experts, weights = honest(*args, **kw)
                    TAPS["routes"].append(experts[:, 0])
                    return experts, weights
                return route
            with coarse_cca.standing_in("route", tapped):
                return CCAMoELM.perturbed_apply(self, *a)

    forms = {"bf16": (Tapped, jnp.bfloat16),
             "fp8_inputs": (coarse_cca.Fp8Cca, jnp.bfloat16),
             "no_value_shift": (coarse_cca.NoValueShiftCca, jnp.bfloat16),
             "no_mean": (coarse_cca.NoMeanCca, jnp.bfloat16),
             "no_state": (coarse_cca.NoStateCca, jnp.bfloat16),
             "renormalised": (coarse_cca.RenormalisedCca, jnp.bfloat16),
             "whole_rotation": (coarse_cca.WholeRotationCca, jnp.bfloat16),
             "other_rank": (coarse_cca.OtherRankCca, jnp.bfloat16),
             "f32": (Tapped, jnp.float32)}
    if rest:
        forms = {name: forms[name] for name in rest[0].split(",")}
    print(f"device {jax.devices()[0].device_kind}; the forms the cell runs "
          f"(kernel scope): {on_tpu}; seeded_std {config.get('seeded_std')}; "
          f"sizes {ref.describe(config)}")
    lm = CCAMoELM(**kwargs)
    shapes = lm.param_shapes()
    spec = lowrank_spec_for(lm, shapes, s["low_rank"])
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
        """((token, layer) routes that differ, those where either side
        chose a held expert and they differ, routes, the system's routes
        into a held expert)."""
        any_, held_, n, into = 0, 0, 0, 0
        for g, w in zip(got_routes, want_routes):
            differ = g != w

            def here(x):
                return (x >= first) & (x < first + held)
            any_ = any_ + jnp.sum(differ)
            held_ = held_ + jnp.sum(differ & (here(g) | here(w)))
            into = into + jnp.sum(here(g))
            n += g.shape[0]
        return any_, held_, n, into

    worst = {name: {"fit_rel": [], "bc": [], "route": [], "held": []}
             for name in forms}
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
                got, got_bc, load, routes = fn(trees[dtype], noise, c, tokens)
                rel = abs(float(got) - want) / max(abs(want + log_v), floor)
                bc = float(np.abs(np.asarray(got_bc) - want_bc).max())
                worst[name]["fit_rel"].append(rel)
                worst[name]["bc"].append(bc)
                said = f"{name} rel {rel:.4g} bc {bc:.4g}"
                if routes:
                    any_, held_, n, into = (
                        int(x) for x in differing(routes, want_routes))
                    assert into == int(load.sum()), (into, load)
                    worst[name]["route"].append(any_ / n)
                    worst[name]["held"].append(held_ / n)
                    said += (f" routes {any_}/{n} held {held_}/{n} "
                             f"into held {into} load max/mean "
                             f"{float(load.max() / load.mean()):.3f}")
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
                     f"{max(w['route']):.4g}), with a held expert on either "
                     f"side {np.median(w['held']):.4g} "
                     f"({min(w['held']):.4g} to {max(w['held']):.4g})")
        print(said)


if __name__ == "__main__":
    main()
