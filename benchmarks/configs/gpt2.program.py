"""The GPT-2 configurations as the program runs them: the model through
``fluxmpi_tpu.models.TransformerLM``, the loss through its fused
cross-entropy head, ``optax.adamw``, and the two maps between the
reference's weight layout and the program's parameter tree (reshapes
and slices only: the numbers are the benchmark's own, from the seed).
"""

from __future__ import annotations

import jax.numpy as jnp


def build_model(cfg: dict, attention: str = "flash"):
    from fluxmpi_tpu.models import TransformerLM

    return TransformerLM(
        vocab_size=cfg["vocab_size"], max_len=cfg["n_positions"],
        num_layers=cfg["n_layer"], d_model=cfg["n_embd"],
        num_heads=cfg["n_head"], d_ff=cfg["n_inner"],
        dropout=cfg["attn_pdrop"], ln_eps=cfg["layer_norm_epsilon"],
        dtype=jnp.dtype(cfg["compute_dtype"]), attention=attention,
    )


def make_loss(model):
    def loss_fn(params, model_state, batch):
        x, y = batch
        return model.apply(params, x, train=True, targets=y).mean(), model_state

    return loss_fn


def make_optimizer(opt: dict):
    import optax

    return optax.adamw(**opt)


def grad_state(opt_state):
    """Adam's first moment: the gradient as the optimizer keeps it."""
    return opt_state[0].mu


def to_program(w: dict, cfg: dict):
    """Reference layout -> ``(variables, model_state)`` of the program."""
    heads = cfg["n_head"]
    d = cfg["n_embd"]
    hd = d // heads
    lay = w["layers"]
    blocks = {}
    for i in range(cfg["n_layer"]):
        def proj(wn, bn):
            return {"kernel": lay[wn][i].reshape(d, heads, hd),
                    "bias": lay[bn][i].reshape(heads, hd)}

        blocks[f"block_{i}"] = {
            "ln1": {"scale": lay["ln1_g"][i], "bias": lay["ln1_b"][i]},
            "attn": {
                "query": proj("wq", "bq"), "key": proj("wk", "bk"),
                "value": proj("wv", "bv"),
                "out": {"kernel": lay["wo"][i].reshape(heads, hd, d),
                        "bias": lay["bo"][i]},
            },
            "ln2": {"scale": lay["ln2_g"][i], "bias": lay["ln2_b"][i]},
            "ff1": {"kernel": lay["w1"][i], "bias": lay["b1"][i]},
            "ff2": {"kernel": lay["w2"][i], "bias": lay["b2"][i]},
        }
    blocks["ln_out"] = {"scale": w["lnf_g"], "bias": w["lnf_b"]}
    params = {"embed": {"embedding": w["wte"]}, "pos_embed": w["wpe"],
              "encoder": blocks}
    return {"params": params}, None


def from_program(variables, model_state, cfg: dict) -> dict:
    """The program's parameter-shaped tree (parameters, a gradient
    moment, a difference of two) back in the reference's layout."""
    del model_state
    p = variables["params"]
    enc = p["encoder"]
    d = cfg["n_embd"]
    n = cfg["n_layer"]

    def stack(get):
        return jnp.stack([get(enc[f"block_{i}"]) for i in range(n)])

    layers = {
        "ln1_g": stack(lambda b: b["ln1"]["scale"]),
        "ln1_b": stack(lambda b: b["ln1"]["bias"]),
        "ln2_g": stack(lambda b: b["ln2"]["scale"]),
        "ln2_b": stack(lambda b: b["ln2"]["bias"]),
        "wo": stack(lambda b: b["attn"]["out"]["kernel"].reshape(d, d)),
        "bo": stack(lambda b: b["attn"]["out"]["bias"]),
        "w1": stack(lambda b: b["ff1"]["kernel"]),
        "b1": stack(lambda b: b["ff1"]["bias"]),
        "w2": stack(lambda b: b["ff2"]["kernel"]),
        "b2": stack(lambda b: b["ff2"]["bias"]),
    }
    for short, name in (("q", "query"), ("k", "key"), ("v", "value")):
        layers[f"w{short}"] = stack(
            lambda b, name=name: b["attn"][name]["kernel"].reshape(d, d)
        )
        layers[f"b{short}"] = stack(
            lambda b, name=name: b["attn"][name]["bias"].reshape(d)
        )
    return {"wte": p["embed"]["embedding"], "wpe": p["pos_embed"],
            "lnf_g": enc["ln_out"]["scale"], "lnf_b": enc["ln_out"]["bias"],
            "layers": layers}


def make_dataset(cfg: dict, data: dict, seed: int):
    """Seeded token rows as next-token ``(inputs, targets)`` arrays."""
    import numpy as np

    rows, seq = data["rows"], data["seq_len"]
    tokens = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], size=(rows, seq + 1), dtype=np.int32
    )
    return tokens[:, :-1], tokens[:, 1:]


def items_per_row(data: dict) -> int:
    return data["seq_len"]
