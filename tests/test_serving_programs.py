"""The two paged serving programs of every family are THE SAME PROGRAMS
they were (models/decode.py ``paged_prefill_chunk`` /
``paged_decode_step``): a digest of the sorted multiset of each
program's equations, sub-jaxprs included, taken by this file's own code
on the commit before ISSUE 52 (534db25), where the attention layer body
was spelled five times and the layers were driven three ways.

An equation is its primitive, the place it is nested in (``scan/cond``),
its operands' and results' avals (a literal operand by its value) and
its static parameters: no variable name, no source line.  A refactor
that traces the same operations in another order keeps every digest; one
added, dropped or reshaped operation moves it.  ``SCOPED`` holds the
same multisets with each equation's ``named_scope`` stack beside it,
shape-only equations (``reshape`` / ``squeeze`` / ``slice`` /
``broadcast_in_dim``, which compile to nothing) left out: what a profile
of the device shows under each name is what it showed.

To re-take after a change that MEANS to move a program:
``python tests/test_serving_programs.py`` prints both tables.
"""

import hashlib
import re

import pytest

# one toy configuration a family (tests/test_lfm2_serving.py ONE_KIND,
# test_eva_serving.py, test_lfm2_serving.py, test_afmoe_serving.py)
FAMILIES = {
    # one kind of layer, a mixture in each
    "mixtral": dict(
        fields=dict(vocab=128, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, d_ff=96, n_experts=4),
        page=4, chunk=8, pages=9, table=12,
    ),
    # EVA attention: a ring of exact pages and the chunks' summaries
    "evabyte": dict(
        fields=dict(vocab=320, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=4, d_ff=96, attention="eva", window_size=32,
                    chunk_size=4, norm_unit_offset=True,
                    tie_embeddings=False, n_pred_heads=8,
                    rope_theta=100000.0, rms_norm_eps=1e-5),
        page=4, chunk=8, pages=9, table=12,
    ),
    # conv layers among attention layers, a mixture in all but the first
    "lfm2": dict(
        model={
            "model_type": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False,
            "hidden_size": 64, "intermediate_size": 96,
            "moe_intermediate_size": 48,
            "layer_types": ["conv", "full_attention", "conv", "conv", "conv",
                            "full_attention", "conv", "conv", "conv"],
            "norm_eps": 1e-5, "norm_topk_prob": True,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "num_dense_layers": 1, "num_experts": 8,
            "num_experts_per_tok": 2, "num_hidden_layers": 9,
            "rope_parameters": {"rope_theta": 1000000,
                                "rope_type": "default"},
            "routed_scaling_factor": 1, "use_expert_bias": True,
            "vocab_size": 128, "tie_word_embeddings": True, "qk_norm": True,
            "router_activation": "sigmoid",
        },
        page=4, chunk=8, pages=9, table=12, slots=3,
    ),
    # window layers among full ones, an output gate, four norms a layer
    "afmoe": dict(
        model={
            "model_type": "afmoe", "hidden_size": 64, "head_dim": 32,
            "intermediate_size": 96, "moe_intermediate_size": 48,
            "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
            "sliding_window": 32, "num_attention_heads": 4,
            "num_key_value_heads": 2, "num_dense_layers": 1,
            "num_experts": 8, "num_experts_per_tok": 2,
            "num_shared_experts": 1, "num_hidden_layers": 5,
            "rms_norm_eps": 1e-5, "rope_theta": 10000, "rope_scaling": None,
            "route_norm": True, "route_scale": 2.826,
            "score_func": "sigmoid", "mup_enabled": True, "n_group": 1,
            "topk_group": 1, "tie_word_embeddings": False,
            "vocab_size": 128, "qk_norm": True, "attention_gate": True,
            "sandwich_norm": True, "nope_on_full_attention": True,
            "use_expert_bias": True, "route_norm_eps": 1e-20,
        },
        page=16, chunk=16, pages=9, table=3 + 8, slots=3, ring=3,
    ),
}

# (family, program, what is patched or passed beside the defaults)
CASES = {
    f"{family}-{program}-{kernel or 'gather'}": (family, program,
                                                 dict(kernel=kernel))
    for family in FAMILIES
    for program in ("prefill", "decode")
    for kernel in (None, "interpret")
}
CASES.update({
    # a decode step over the pool rides in the chunk's program
    "evabyte-riders-gather": ("evabyte", "prefill",
                              dict(kernel=None, riders=True)),
    "evabyte-riders-interpret": ("evabyte", "prefill",
                                 dict(kernel="interpret", riders=True)),
    # a quantized arena: scales beside the pages, the gather path
    "mixtral-prefill-int8": ("mixtral", "prefill", dict(kv_dtype="int8")),
    "mixtral-decode-int8": ("mixtral", "decode", dict(kv_dtype="int8")),
    # a history longer than a block: the chunk's running softmax (the
    # path every long-context cell takes)
    "mixtral-prefill-blocks": ("mixtral", "prefill", dict(block=16)),
    "afmoe-prefill-blocks": ("afmoe", "prefill", dict(block=32)),
})

DIGESTS = {
    "afmoe-decode-gather": "f61b6006b086877b",
    "afmoe-decode-interpret": "9c7da293e47ce4fb",
    "afmoe-prefill-blocks": "02a176ab7498332a",
    "afmoe-prefill-gather": "4e44341d3a3d5aea",
    "afmoe-prefill-interpret": "4e44341d3a3d5aea",
    "evabyte-decode-gather": "7f3013c00c122941",
    "evabyte-decode-interpret": "8dfd7bb705985c0b",
    "evabyte-prefill-gather": "57b5eb9008c9c593",
    "evabyte-prefill-interpret": "57b5eb9008c9c593",
    "evabyte-riders-gather": "4d16195a06bdf528",
    "evabyte-riders-interpret": "463a7e78ed57bb0f",
    "lfm2-decode-gather": "5391ef9c1387b936",
    "lfm2-decode-interpret": "cdfe9d37e9a338ba",
    "lfm2-prefill-gather": "0705432df0a6da0f",
    "lfm2-prefill-interpret": "0705432df0a6da0f",
    "mixtral-decode-gather": "589e4d804b50aaa1",
    "mixtral-decode-int8": "aec27aa2adf9b60d",
    "mixtral-decode-interpret": "78428602ef9185b4",
    "mixtral-prefill-blocks": "ee4fc15323139aef",
    "mixtral-prefill-gather": "2bc327dfda22f670",
    "mixtral-prefill-int8": "d9bfaadda0c7bd2b",
    "mixtral-prefill-interpret": "2bc327dfda22f670",
}
SCOPED = {
    "afmoe-decode-gather": "e236233b82ba96f8",
    "afmoe-decode-interpret": "f6f3aee1b76664fd",
    "afmoe-prefill-blocks": "d1ebde1cb9f3c425",
    "afmoe-prefill-gather": "f48d930b37357a57",
    "afmoe-prefill-interpret": "f48d930b37357a57",
    "evabyte-decode-gather": "0b8fc1564293eb77",
    "evabyte-decode-interpret": "46da7dd1bc962302",
    "evabyte-prefill-gather": "ee5b23a36a22d3b4",
    "evabyte-prefill-interpret": "ee5b23a36a22d3b4",
    "evabyte-riders-gather": "cbb1aff7a0613022",
    "evabyte-riders-interpret": "0f7739c214ba1e3c",
    "lfm2-decode-gather": "eb19f58bbd31febf",
    "lfm2-decode-interpret": "1829941763e8254a",
    "lfm2-prefill-gather": "c642535fcbbab70f",
    "lfm2-prefill-interpret": "c642535fcbbab70f",
    "mixtral-decode-gather": "99cde4bcb349c1e6",
    "mixtral-decode-int8": "ec76aa35ee0f3233",
    "mixtral-decode-interpret": "d5e39cf4828c733f",
    "mixtral-prefill-blocks": "6d44d2a729acca47",
    "mixtral-prefill-gather": "71b40c9a4ff92db5",
    "mixtral-prefill-int8": "f14bacac79a23760",
    "mixtral-prefill-interpret": "71b40c9a4ff92db5",
}

_SHAPE_ONLY = ("reshape", "squeeze", "slice", "broadcast_in_dim")


def _static(value):
    """A parameter of an equation without what moves between two
    traces of the same program: sub-jaxprs (walked on their own),
    addresses, functions."""
    from jax.extend import core

    if isinstance(value, (core.Jaxpr, core.ClosedJaxpr)):
        return "<jaxpr>"
    if isinstance(value, (tuple, list)):
        return "(" + ",".join(_static(v) for v in value) + ")"
    if isinstance(value, dict):
        return "{" + ",".join(
            f"{k}:{_static(v)}" for k, v in sorted(value.items())
        ) + "}"
    if callable(value) and not isinstance(value, type):
        return f"<{type(value).__name__}>"
    return re.sub(r"0x[0-9a-f]+", "0x", repr(value))


def _jaxprs_of(value):
    from jax.extend import core

    if isinstance(value, core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _jaxprs_of(v)


def equations(jaxpr, scoped=False, path=""):
    """One line an equation of ``jaxpr`` and of every jaxpr under it."""
    from jax.extend import core

    def aval(v):
        if isinstance(v, core.Literal):
            return f"{v.aval.str_short()}={v.val!r}"
        return v.aval.str_short()

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        inner = [
            sub for _k, v in sorted(eqn.params.items())
            for sub in _jaxprs_of(v)
        ]
        for sub in inner:
            yield from equations(sub, scoped, f"{path}{name}/")
        if scoped and name in _SHAPE_ONLY:
            continue
        params = ";".join(
            f"{k}={_static(v)}" for k, v in sorted(eqn.params.items())
        )
        line = "|".join((
            path + name, ",".join(aval(v) for v in eqn.invars),
            ",".join(aval(v) for v in eqn.outvars), params,
        ))
        if scoped:
            line += "|" + str(eqn.source_info.name_stack)
        yield line


def digest(jaxpr, scoped=False):
    lines = sorted(equations(jaxpr, scoped))
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]


def _config(family):
    import json
    import tempfile

    import jax.numpy as jnp

    from dcos_commons_tpu.models import TransformerConfig, config_from_env

    spec = FAMILIES[family]
    if "fields" in spec:
        return TransformerConfig(
            dtype=jnp.float32, remat=False, **spec["fields"]
        )
    with tempfile.NamedTemporaryFile("w", suffix=".json") as f:
        json.dump(spec["model"], f)
        f.flush()
        return config_from_env(
            {"MODEL_CONFIG": f.name}, dtype=jnp.float32, remat=False
        )


def trace(name, monkeypatch):
    """The jaxpr of case ``name``: the program over abstract weights,
    arena and rows, as the pool would call it."""
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models import decode as D
    from dcos_commons_tpu.models import init_params

    family, program, how = CASES[name]
    spec = FAMILIES[family]
    config = _config(family)
    kernel = how.get("kernel")
    monkeypatch.setattr(
        D, "decode_attention_kernel",
        lambda config, cache: None if "k_scale" in cache else kernel,
    )
    if "block" in how:
        monkeypatch.setattr(D, "CHUNK_ATTENTION_BLOCK", how["block"])
    ring = spec.get("ring", 0)
    slots = spec.get("slots", 3)
    params = jax.eval_shape(lambda: init_params(config, jax.random.key(0)))
    cache = jax.eval_shape(lambda: D.init_paged_kv_cache(
        config, spec["pages"], spec["page"],
        kv_dtype=how.get("kv_dtype", "native"), slots=slots,
        window_pages=slots * ring + 1 if ring else 0,
    ))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    step = (i32(slots), i32(slots), i32(slots, spec["table"]))
    if program == "decode":
        fn = lambda p, c, tok, pos, tables: D.paged_decode_step(  # noqa: E731
            config, p, c, tok, pos, tables, ring
        )
        return jax.make_jaxpr(fn)(params, cache, *step).jaxpr
    riders = step if how.get("riders") else ()

    def fn(p, c, tokens, table, start, true_len, slot, *riders):
        return D.paged_prefill_chunk(
            config, p, c, tokens, table, start, true_len, slot,
            riders or None, ring,
        )

    return jax.make_jaxpr(fn)(
        params, cache, i32(1, spec["chunk"]), i32(spec["table"]), i32(),
        i32(), i32(), *riders
    ).jaxpr


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_serving_program_is_the_equations_it_was(name, monkeypatch):
    jaxpr = trace(name, monkeypatch)
    assert digest(jaxpr) == DIGESTS[name], (
        "an operation was added, dropped or reshaped"
    )
    assert digest(jaxpr, scoped=True) == SCOPED[name], (
        "an operation moved to another named_scope"
    )


def test_the_digest_sees_an_operation_and_no_order():
    """What the table above can and cannot tell apart."""
    import jax
    import jax.numpy as jnp

    x = jnp.zeros((2, 3))
    a = jax.make_jaxpr(lambda x: (jnp.sin(x) + 1, jnp.cos(x)))(x).jaxpr
    b = jax.make_jaxpr(lambda x: (jnp.cos(x), jnp.sin(x) + 1)[::-1])(x).jaxpr
    c = jax.make_jaxpr(lambda x: (jnp.sin(x) + 2, jnp.cos(x)))(x).jaxpr
    d = jax.make_jaxpr(
        lambda x: (jnp.sin(x.reshape(3, 2)) + 1, jnp.cos(x))
    )(x).jaxpr
    assert digest(a) == digest(b)
    assert len({digest(a), digest(c), digest(d)}) == 3

    def named(x):
        with jax.named_scope("named"):
            return jnp.sin(x) + 1, jnp.cos(x)

    e = jax.make_jaxpr(named)(x).jaxpr
    assert digest(a) == digest(e)
    assert digest(a, scoped=True) != digest(e, scoped=True)


@pytest.mark.parametrize("family", ["mixtral", "afmoe"])
def test_parts_side_by_side_attend_as_one_part_does(family):
    """The contract every part keeps (``Part``): the rows of a layer's
    operand may be several parts', each handed its rows flat.  A decode
    step over four slots as one part a layer kind, and as two parts of
    two slots each, leaves the same hidden states and the same arenas:
    what a decode step riding behind a grouped-query chunk will lean
    on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dcos_commons_tpu.models import decode as D
    from dcos_commons_tpu.models import init_params

    spec, config = FAMILIES[family], _config(family)
    ring, slots = spec.get("ring", 0), 4
    params = init_params(config, jax.random.key(0))
    cache = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(1), a.shape, a.dtype),
        D.init_paged_kv_cache(
            config, spec["pages"], spec["page"], slots=slots,
            window_pages=slots * ring + 1 if ring else 0,
        ),
    )
    # every slot live: a ring and two pages of history each
    tables = np.zeros((slots, spec["table"]), np.int32)
    for s in range(slots):
        tables[s, :ring] = 1 + s * ring + np.arange(ring)
        tables[s, ring:ring + 2] = 1 + 2 * s + np.arange(2)
    tables = jnp.asarray(tables)
    pos = jnp.asarray([0, 3, spec["page"], spec["page"] + 2], jnp.int32)
    x = D._embed(config, params, jnp.arange(slots))[:, None, :]

    def run(groups):
        parts, live = {}, []
        for rows in groups:
            mine, alive = D._step_parts(
                config, cache, pos[rows], tables[rows], ring
            )
            live.append(alive)
            for kind, part in mine.items():
                parts.setdefault(kind, []).extend(part)
        return D._serving_trunk(
            config, params, cache, x, pos[:, None], parts, None,
            jnp.concatenate(live),
        )

    whole = jax.jit(lambda: run([slice(0, slots)]))()
    halves = jax.jit(lambda: run([slice(0, 2), slice(2, slots)]))()
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(halves)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
    monkeypatch = pytest.MonkeyPatch()
    tables = {"DIGESTS": {}, "SCOPED": {}}
    for case in sorted(CASES):
        with monkeypatch.context() as patch:
            jaxpr = trace(case, patch)
        tables["DIGESTS"][case] = digest(jaxpr)
        tables["SCOPED"][case] = digest(jaxpr, scoped=True)
        if len(sys.argv) > 1:
            # every line, for a diff of two commits
            for scoped in (False, True):
                with open(f"{sys.argv[1]}/{case}.{int(scoped)}", "w") as f:
                    f.write("\n".join(sorted(equations(jaxpr, scoped))))
    for table, rows in tables.items():
        print(f"{table} = {{")
        for case, value in rows.items():
            print(f'    "{case}": "{value}",')
        print("}")
