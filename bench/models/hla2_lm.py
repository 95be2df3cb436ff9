"""The program's side of the HLA2 language model: its configuration object,
its parameter tree and its compiled entry points.

``program_config`` turns a configuration file of ``bench/configs`` into
the program's ``ModelConfig``; ``to_program`` re-arranges the named weights
of ``hla2_lm_ref.make_weights`` into the program's parameter tree and checks
every leaf's shape and dtype against the tree the program itself declares.
"""

from __future__ import annotations

import jax


def program_config(c: dict):
    from repro.models.config import HLAConfig, ModelConfig

    return ModelConfig(
        name=c["name"], n_layers=c["n_layers"], d_model=c["d_model"],
        n_heads=c["n_heads"], n_kv_heads=c["n_kv_heads"], d_ff=c["d_ff"],
        vocab=c["vocab"], mixer="hla2", mlp="swiglu",
        hla=HLAConfig(variant="hla2", chunk=c["chunk"], decay="learned"),
        remat=c["remat"], dtype=c["dtype"], param_dtype=c["param_dtype"],
        norm_eps=c["norm_eps"],
    )


def to_program(W: dict) -> dict:
    """Named reference weights -> the program's (layer-stacked) tree."""
    k = lambda a: {"kernel": a}  # noqa: E731
    return {
        "embed": {"embedding": W["embed"]},
        "layers": {
            "ln1": {"scale": W["ln1"]},
            "ln2": {"scale": W["ln2"]},
            "mixer": {
                "wq": k(W["wq"]), "wk": k(W["wk"]), "wv": k(W["wv"]),
                "wo": k(W["wo"]), "out_scale": W["out_scale"],
                "decay_a": W["decay_a"],
            },
            "mlp": {"wi_gate": k(W["w_gate"]), "wi_up": k(W["w_up"]),
                    "wo": k(W["w_down"])},
        },
        "final_norm": {"scale": W["final_norm"]},
        "unembed": {"kernel": W["unembed"]},
    }


def from_program(tree: dict) -> dict:
    """The program's tree -> named leaves (inverse of ``to_program``)."""
    lay = tree["layers"]
    mix, mlp = lay["mixer"], lay["mlp"]
    return {
        "embed": tree["embed"]["embedding"],
        "ln1": lay["ln1"]["scale"], "ln2": lay["ln2"]["scale"],
        "wq": mix["wq"]["kernel"], "wk": mix["wk"]["kernel"],
        "wv": mix["wv"]["kernel"], "wo": mix["wo"]["kernel"],
        "out_scale": mix["out_scale"], "decay_a": mix["decay_a"],
        "w_gate": mlp["wi_gate"]["kernel"], "w_up": mlp["wi_up"]["kernel"],
        "w_down": mlp["wo"]["kernel"],
        "final_norm": tree["final_norm"]["scale"],
        "unembed": tree["unembed"]["kernel"],
    }


def check_tree(cfg, tree) -> None:
    """Raise unless ``tree`` matches the program's declared parameters
    leaf for leaf (structure, shape and dtype)."""
    from repro.distributed import steps
    from repro.models.param import abstract_params

    want = abstract_params(steps.model_specs(cfg))
    got = jax.eval_shape(lambda: tree) if not isinstance(
        jax.tree.leaves(tree)[0], jax.ShapeDtypeStruct) else tree
    ws = jax.tree.structure(want)
    gs = jax.tree.structure(got)
    if ws != gs:
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"{gs}\nvs\n{ws}")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {a.shape}/{a.dtype} != {b.shape}/{b.dtype}")
