"""The program's side of the hybrid HLA2 + softmax language model: its
configuration object and its parameter tree.

``program_config`` turns a configuration file of ``bench/configs`` (the
source's own keys, as run) into the program's ``ModelConfig``: the period
of ``layer_types`` becomes its ``layer_pattern``, with the configured HLA2
mixer in the linear-attention positions and softmax attention in the
full-attention ones.  ``to_program`` re-arranges the named weights of
``hla2_hybrid_lm_ref.make_weights`` (stacked over periods, one name per
position) into the program's tree; ``check_tree`` checks every leaf
against the tree the program declares.
"""

from __future__ import annotations

from bench.models.hla2_hybrid_lm_ref import LINEAR, dims
from bench.models.hla2_lm import check_tree  # noqa: F401  (interface)


def program_config(c: dict):
    from repro.models.config import HLAConfig, ModelConfig

    m = dims(c)
    if not (c["linear_num_key_heads"] == c["linear_num_value_heads"]
            == c["num_key_value_heads"]):
        raise ValueError("the program has one key/value head count for "
                         "every layer")
    return ModelConfig(
        name=c["name"], n_layers=m["L"], d_model=m["d"], n_heads=m["H"],
        n_kv_heads=m["Hk"], d_ff=m["ff"], vocab=m["V"], mixer="hla2",
        mlp="swiglu", rope_theta=None, norm_eps=c["rms_norm_eps"],
        layer_pattern=tuple("mixer" if t == LINEAR else "attn"
                            for t in m["period"]),
        hla=HLAConfig(variant="hla2", chunk=c["chunk"], decay=c["decay"],
                      d_key=m["dk"], d_value=m["dv"]),
        remat=c["remat"], dtype=c["dtype"], param_dtype=c["param_dtype"],
    )


def to_program(W: dict) -> dict:
    """Named reference weights -> the program's period-stacked tree."""
    k = lambda a: {"kernel": a}  # noqa: E731
    groups = {}
    i = 0
    while f"p{i}_ln1" in W:
        p = lambda name: W[f"p{i}_{name}"]  # noqa: E731
        pos = {"ln1": {"scale": p("ln1")}, "ln2": {"scale": p("ln2")},
               "mlp": {"wi_gate": k(p("w_gate")), "wi_up": k(p("w_up")),
                       "wo": k(p("w_down"))}}
        proj = {name: k(p(name)) for name in ("wq", "wk", "wv", "wo")}
        if f"p{i}_decay_a" in W:  # a linear-attention position: HLA2
            pos["mixer"] = dict(proj, out_scale=p("out_scale"),
                                decay_a=p("decay_a"))
        else:
            pos["attn"] = proj
        groups[f"pos{i}"] = pos
        i += 1
    return {"embed": {"embedding": W["embed"]}, "groups": groups,
            "final_norm": {"scale": W["final_norm"]},
            "unembed": {"kernel": W["unembed"]}}
