"""The parameter tree of this family, as the program's checkpoint format
has it: ``embed``, an output head of its own ``lm_head``
(``[hidden, num_pred_heads * vocab]``, ``x @ W``), ``final_norm`` and
one node ``layers`` whose leaves are stacked over the layers.  The
norms store their weight's OFFSET from one (``norm_add_unit_offset``),
so they are drawn around zero; ``eva_phi`` and ``eva_mu`` (a vector of
``head_dim`` a key-value head a layer) are drawn at the checkpoint's
``init_std``.
"""

from __future__ import annotations


def weight_specs(model: dict) -> list:
    d, n = model["hidden_size"], model["num_hidden_layers"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // h
    f, v = model["intermediate_size"], model["vocab_size"]
    offset = bool(model.get("norm_add_unit_offset"))
    norm = ("normal", 0.1) if offset else ("around_one", 0.1)
    specs = [
        (("embed",), (v, d), "normal", d ** -0.5, "served"),
        (("layers", "attn_norm"), (n, d)) + norm + ("served",),
        (("layers", "wq"), (n, d, h * hd), "normal", d ** -0.5, "served"),
        (("layers", "wk"), (n, d, kv * hd), "normal", d ** -0.5, "served"),
        (("layers", "wv"), (n, d, kv * hd), "normal", d ** -0.5, "served"),
        (("layers", "wo"), (n, h * hd, d), "normal", (h * hd) ** -0.5,
         "served"),
        (("layers", "mlp_norm"), (n, d)) + norm + ("served",),
        (("final_norm",), (d,)) + norm + ("served",),
        (("layers", "w_gate"), (n, d, f), "normal", d ** -0.5, "served"),
        (("layers", "w_up"), (n, d, f), "normal", d ** -0.5, "served"),
        (("layers", "w_down"), (n, f, d), "normal", f ** -0.5, "served"),
        (("layers", "eva_phi"), (n, kv, hd), "normal", model["init_std"],
         "served"),
        (("layers", "eva_mu"), (n, kv, hd), "normal", model["init_std"],
         "served"),
    ]
    if not model.get("tie_word_embeddings"):
        specs.append(
            (("lm_head",), (d, model.get("num_pred_heads", 1) * v), "normal",
             d ** -0.5, "served")
        )
    return specs
