"""How the snapshots of ``policy_seam_parent.py`` are taken: the same
functions read the parent commit (:func:`main`, run there, wrote what was
pasted) and the tree under test (tests/test_policy_seam.py,
tests/test_policy_contract.py)."""

import glob
import hashlib
import json
import os
import re
import sys

import jax

from estorch_tpu.parallel.mesh import (hyperscale_mesh, match_partition_rules,
                                       sharding_summary)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MESHES = ((1, 1), (1, 4), (2, 4))


def published():
    """``{configuration: (model, optimizer)}`` of the sequence models of
    ``benchmark/configs/*.json`` at their PUBLISHED sizes (read, never
    edited; nothing is materialised: shapes only)."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.files import resolve

    out = {}
    for path in sorted(glob.glob(
            os.path.join(ROOT, "benchmark", "configs", "*.json"))):
        with open(path) as f:
            kwargs = json.load(f)["build"]["kwargs"]
        policy = resolve(kwargs.get("policy"))
        if not hasattr(policy, "param_shapes"):
            continue                        # the two MLP cells: no rule cuts
        out[os.path.basename(path)[:-5]] = (
            policy(**resolve(kwargs["policy_kwargs"])),
            resolve(kwargs["optimizer"])(**kwargs["optimizer_kwargs"]))
    return out


def leaf_specs(model, optimizer, mesh_shape, rules) -> dict:
    """``{leaf path: PartitionSpec}`` of every parameter leaf and every
    optimiser-state leaf of ``model`` under ``rules`` on a ``(pop, model)``
    mesh of ``mesh_shape``, as the param-sharded engine resolves them."""
    pop, shards = mesh_shape
    mesh = hyperscale_mesh(pop, shards, jax.devices()[:pop * shards])
    shapes = model.param_shapes()
    trees = {"params": shapes,
             "opt_state": jax.eval_shape(optimizer.init, shapes)}
    out = {}
    for name, tree in trees.items():
        shardings = match_partition_rules(rules, tree, mesh,
                                          log_unmatched=False)
        out.update({f"{name}/{path}": spec for path, spec in
                    sharding_summary(tree, shardings).items()})
    return out


def _folded(path: str) -> str:
    path = re.sub(r"^(params|opt_state/0/(mu|nu))/", "*/", path)
    return re.sub(r"layer_\d+", "layer_*", path)


def digest(specs: dict) -> dict:
    """What is kept of ``specs``: the count of leaves and a hash of every
    (path, spec), which hold a tree to the snapshot leaf for leaf, and for
    the reader of a failure the specs with the layers' numbers and the
    tree (the parameters, Adam's two moments) folded into ``*`` wherever
    the folded leaves agree; a leaf as it is where they do not."""
    folded: dict = {}
    for path, spec in specs.items():
        folded.setdefault(_folded(path), set()).add(spec)
    by_path = {}
    for path, spec in sorted(specs.items()):
        fold = _folded(path)
        by_path[fold if len(folded[fold]) == 1 else path] = spec
    text = "\n".join(f"{p}\t{s}" for p, s in sorted(specs.items()))
    return {"leaves": len(specs),
            "sha256": hashlib.sha256(text.encode()).hexdigest(),
            "specs": by_path}


def main(out):
    """Write ``policy_seam_parent.py``'s three literals as THIS tree gives
    them: run at the parent of a PR that means to move one (``PYTHONPATH=.
    JAX_PLATFORMS=cpu python tests/seam_snapshots.py <file>`` from the root
    of a copy of that commit), and paste."""
    import pprint

    import conftest  # noqa: F401  (the suite's 8 virtual CPU devices)
    import test_policy_contract as contract
    from estorch_tpu.parallel.mesh import (DEFAULT_PARTITION_RULES,
                                           partition_rules_to_json)

    shardings, builds = {}, {}
    for name, (model, optimizer) in published().items():
        rules = (getattr(model.declaration(), "partition_rules", ())
                 + DEFAULT_PARTITION_RULES)
        for mesh in MESHES:
            shardings[f"{name} {mesh[0]}x{mesh[1]}"] = digest(
                leaf_specs(model, optimizer, mesh, rules))
    for name in sorted(contract.SEQUENCE_MODELS) + ["mlp_replicated",
                                                    "mlp_sharded"]:
        es = contract.build(name)
        config = es.run_manifest()["config"]
        config.pop("partition_rules", None)
        builds[name] = {"config": config,
                        "gauges": es.obs.counters.snapshot(),
                        "sized": contract.sized(es.engine)}
    with open(out, "w") as f:
        for name, value in (("SHARDINGS", shardings), ("BUILDS", builds),
                            ("RULES_JSON", partition_rules_to_json(
                                DEFAULT_PARTITION_RULES))):
            f.write(f"{name} = {pprint.pformat(value, width=79)}\n\n")


if __name__ == "__main__":
    main(sys.argv[1])
