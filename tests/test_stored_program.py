"""A warm start LOADS the pool's two programs (ISSUE 42): the store
beside the compile cache (utils/stored_program.py), its key, what it
does with an entry that is no good, and what the worker's start-up
clock is told.  CPU, toy sizes, a temporary store directory.
"""

import copy
import dataclasses
import json
import multiprocessing
import os
import pickle
import subprocess
import sys
import time

import numpy as np
import pytest

from dcos_commons_tpu.serve.engine import PagedEngine
from dcos_commons_tpu.testing.chain_model import settled_stats
from dcos_commons_tpu.trace import StartupClock
from dcos_commons_tpu.trace.startup import (
    LOAD_EVENT,
    STORE_EVENT,
    WARM_PROGRAMS,
    _program,
)
from dcos_commons_tpu.trace.steplog import StepLog
from dcos_commons_tpu.utils import compile_cache, stored_program
from dcos_commons_tpu.utils.stored_program import StoredProgram

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"

# the three families the benchmark's cells serve, at toy size: a
# grouped-query mixture, EVA rows (whose chunk carries the tick's
# decode step) and a pattern with conv layers over a mixture
FAMILIES = {
    "gqa-moe": dict(
        vocab=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, n_experts=4, moe_top_k=2,
    ),
    "eva": dict(
        vocab=96, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=48, rms_norm_eps=1e-5, norm_unit_offset=True,
        tie_embeddings=False, n_pred_heads=2, attention="eva",
        window_size=16, chunk_size=4, eva_init_std=0.5,
    ),
    "conv-moe": dict(
        vocab=64, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2,
        d_ff=48, layer_types=("conv", "attention", "conv"),
        n_dense_layers=1, n_experts=4, moe_top_k=2, moe_d_ff=24,
        moe_score="sigmoid", moe_expert_bias=True, qk_norm=True,
    ),
}
SLOTS, MAX_LEN, PAGE, PAGES, CHUNK = 3, 64, 4, 40, 8
POOL = dict(slots=SLOTS, max_len=MAX_LEN, page_tokens=PAGE, pages=PAGES,
            chunk_tokens=CHUNK)


def _config(family, **changed):
    import jax.numpy as jnp

    from dcos_commons_tpu.models import TransformerConfig

    return TransformerConfig(
        dtype=jnp.float32, remat=False, **{**FAMILIES[family], **changed}
    )


def _model(family):
    import jax

    from dcos_commons_tpu.models import init_params

    config = _config(family)
    return config, init_params(config, jax.random.key(7))


def _pool(config, params, riders=True, **changed):
    from dcos_commons_tpu.serve.pool import PagedPoolModel

    return PagedPoolModel(config, params, riders=riders,
                          **{**POOL, **changed})


@pytest.fixture
def store(tmp_path, monkeypatch):
    """A store directory of this test's own, as a process whose
    compile cache lies under ``tmp_path`` would have it."""
    directory = str(tmp_path / "programs")
    monkeypatch.setattr(compile_cache, "programs_dir", lambda: directory)
    return directory


class Heard:
    """Everything ``jax.monitoring`` says while it is open, and a
    start-up clock listening beside it."""

    def __init__(self, tmp_path):
        self.events = []
        self.clock = StartupClock(
            {}, steplog=StepLog(str(tmp_path / "steplog.jsonl"))
        )

    def _on_duration(self, event, duration, fun_name="", **_kwargs):
        self.events.append((event, str(fun_name)))
        self.clock.on_duration(event, duration, fun_name=fun_name)

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )
        return self

    def __exit__(self, *_exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)

    def of_programs(self, *kinds):
        """The events of ``kinds`` that name one of the two programs."""
        return [
            (event, name) for event, name in self.events
            if event in kinds and _program(name) in WARM_PROGRAMS
        ]


def _serve(pool):
    """A short prompt and a long one admitted in one tick: the long
    prompt's later chunks each meet the short row's decode step (a
    rider chunk where the family has one), then several decode steps
    of both."""
    engine = PagedEngine(
        pool.prefill_chunk, pool.decode, SLOTS, MAX_LEN, MAX_LEN - 16,
        page_tokens=PAGE, pages=PAGES, chunk_tokens=CHUNK,
        layout=pool.layout, prefix_cache=False, queue_timeout_s=120,
        resolve_decode_fn=pool.resolve_decode,
        chunk_riders=pool.chunk_riders,
    )
    rng = np.random.default_rng(11)
    vocab = pool.config.vocab
    prompts = [list(map(int, rng.integers(0, vocab, n))) for n in (5, 27)]
    try:
        outs = engine.submit(prompts, 9)
        loop = settled_stats(engine)["loop"]
    finally:
        engine.stop()
    return outs, {
        name: loop[name]
        for name in ("prefill_calls", "prefill_rider_calls", "decode_calls")
    }


# -- a second pool loads what the first stored --------------------------


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_second_pool_loads_both_programs_and_serves_the_same(
        family, store, tmp_path, monkeypatch):
    import jax

    config, params = _model(family)
    with Heard(tmp_path) as first:
        with first.clock.warm():
            pool = _pool(config, params)
            pool.warm()
    assert first.clock.stats["programs"] == {"stored": 0, "compiled": 2}
    # each program was lowered exactly once, stored, and is what is
    # called: serving compiles nothing more
    for program in ("_prefill", "_decode"):
        assert first.of_programs(LOWER).count((LOWER, f"jit({program})")) == 1
        warm = first.clock.stats["warm"][program]
        assert warm["source"] == "compiled"
        assert warm["store_s"] > 0 and warm["load_s"] == 0
    assert sorted(name.split("-")[0] for name in os.listdir(store)) == [
        "_decode", "_prefill",
    ]
    served, calls = _serve(pool)
    assert (calls["prefill_rider_calls"] > 0) == (family == "eva")
    assert pool._prefill_c._cache_size() == 1
    assert pool._decode_c._cache_size() == 1

    with Heard(tmp_path) as second:
        with second.clock.warm():
            again = _pool(config, params)
            again.warm()
        assert second.clock.stats["programs"] == {"stored": 2, "compiled": 0}
        assert second.of_programs(TRACE, LOWER, COMPILE) == []
        for program in ("_prefill", "_decode"):
            warm = second.clock.stats["warm"][program]
            assert warm["source"] == "stored" and warm["load_s"] > 0
            assert warm["trace_s"] == warm["lower_s"] == 0
            assert warm["compile_s"] == warm["store_s"] == 0
        second.clock.ready()
        assert _serve(again) == (served, calls)
        assert second.clock.stats["compiles_after_ready"] == 0
        assert second.of_programs(TRACE, LOWER, COMPILE) == []
    assert again._prefill_c._cache_size() == 1
    assert again._decode_c._cache_size() == 1
    record = [
        json.loads(line) for line in open(tmp_path / "steplog.jsonl")
        if '"startup.warm"' in line
    ][-1]
    assert (record["stored"], record["compiled"]) == (2, 0)
    assert record["load_s"] > 0 and record["store_s"] == 0

    # and a pool of plain ``jax.jit`` functions serves the same
    monkeypatch.setattr(
        stored_program, "StoredProgram",
        lambda fn, donate_argnums, closed_over, directory: jax.jit(
            fn, donate_argnums=donate_argnums
        ),
    )
    plain = _pool(config, params)
    plain.warm()
    assert _serve(plain) == (served, calls)
    assert plain._decode_c._cache_size() == 1


def test_a_pool_over_a_mesh_compiles_and_stores_nothing(store):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    config, params = _model("gqa-moe")
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    pool = _pool(
        config, params,
        cache_sharding=NamedSharding(mesh, P(None, None, None, "tp", None)),
    )
    pool.warm(ahead=False)
    assert pool._prefill_c._cache_size() == 1
    assert pool._decode_c._cache_size() == 1
    assert not os.path.exists(store)


# -- the key -------------------------------------------------------------


def _key(config=None, device=None, environment=None, **pool):
    """The decode program's key for a pool of ``config`` and ``pool``'s
    sizes, from shapes alone."""
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models import init_params
    from dcos_commons_tpu.models.decode import (
        arena_lanes,
        init_paged_kv_cache,
    )
    from dcos_commons_tpu.serve.paging import RowLayout

    config = config or _config("gqa-moe")
    sizes = {**POOL, "kv_dtype": "native", "riders": False, **pool}
    slots = sizes["slots"]
    table = RowLayout(sizes["page_tokens"]).table_len(sizes["max_len"])
    args = (
        jax.eval_shape(lambda: init_params(config, jax.random.key(0))),
        jax.eval_shape(lambda: init_paged_kv_cache(
            config, sizes["pages"] + 1, sizes["page_tokens"],
            sizes["kv_dtype"], slots, arena_lanes(config),
        )),
        jax.ShapeDtypeStruct((1, sizes["chunk_tokens"]), jnp.int32),
        jax.ShapeDtypeStruct((slots, table), jnp.int32),
    )
    device = device or jax.devices()[0]
    return stored_program.program_key(
        "_decode",
        dict(config=config, kv_dtype=sizes["kv_dtype"],
             riders=sizes["riders"]),
        (), stored_program.signature(args),
        environment or stored_program.lowering_environment(device),
    )[0]


def _field_changes():
    """Every field of the configuration, each with another value."""
    base = _config("gqa-moe")
    other = {
        bool: lambda v: not v, int: lambda v: v + 2,
        float: lambda v: v * 1.5 + 0.25, str: lambda v: v + "x",
        tuple: lambda v: v + ("attention",),
    }
    for field in dataclasses.fields(base):
        value = getattr(base, field.name)
        if field.name == "dtype":
            yield field.name, "bfloat16"
        else:
            yield field.name, other[type(value)](value)


@pytest.mark.parametrize("what", [
    *("config." + name for name, _value in _field_changes()),
    "slots", "pages", "page_tokens", "chunk_tokens", "kv_dtype", "riders",
    "source-byte", "jax-version", "libtpu-build", "XLA_FLAGS",
    "LIBTPU_INIT_ARGS", "matmul-precision", "device-count", "program-name",
    "donation",
])
def test_whatever_a_lowering_can_depend_on_changes_the_key(
        what, tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    base = _key()
    assert base == _key()
    key = stored_program.program_key
    if what.startswith("config."):
        name = what.split(".", 1)[1]
        value = dict(_field_changes())[name]
        # the key reads the fields: no check of one against another
        config = copy.copy(_config("gqa-moe"))
        object.__setattr__(
            config, name, getattr(jnp, value) if name == "dtype" else value
        )
        assert key("_decode", dict(config=config), (), ((), ()), {}) != \
            key("_decode", dict(config=_config("gqa-moe")), (), ((), ()), {})
        return
    if what in ("slots", "pages", "page_tokens", "chunk_tokens"):
        changed = _key(**{what: POOL[what] * 2})
    elif what == "kv_dtype":
        changed = _key(kv_dtype="int8")
    elif what == "riders":
        changed = _key(riders=True)
    elif what == "source-byte":
        package = tmp_path / "package"
        (package / "models").mkdir(parents=True)
        (package / "models" / "decode.py").write_text("x = 1\n")
        (package / "notes.txt").write_text("not a source\n")
        device = jax.devices()[0]
        environment = stored_program.lowering_environment(
            device, str(package)
        )
        base = _key(environment=environment)
        (package / "notes.txt").write_text("still not a source\n")
        stored_program.source_digest.cache_clear()
        assert base == _key(environment=stored_program.lowering_environment(
            device, str(package)
        ))
        (package / "models" / "decode.py").write_text("x = 2\n")
        stored_program.source_digest.cache_clear()
        changed = _key(environment=stored_program.lowering_environment(
            device, str(package)
        ))
    elif what == "jax-version":
        monkeypatch.setattr(jax, "__version__", jax.__version__ + ".1")
        changed = _key()
    elif what in ("libtpu-build", "device-count"):
        environment = stored_program.lowering_environment(jax.devices()[0])
        field = {"libtpu-build": "platform_version",
                 "device-count": "device_count"}[what]
        changed = _key(environment={
            **environment, field: f"{environment[field]}1",
        })
    elif what in ("XLA_FLAGS", "LIBTPU_INIT_ARGS"):
        monkeypatch.setenv(what, os.environ.get(what, "") + " --xla_x=1")
        changed = _key()
    elif what == "matmul-precision":
        with jax.default_matmul_precision("float32"):
            changed = _key()
    elif what == "program-name":
        assert key("_decode", None, (), ((), ()), {}) != \
            key("_prefill", None, (), ((), ()), {})
        return
    elif what == "donation":
        assert key("_decode", None, (), ((), ()), {}) != \
            key("_decode", None, (1,), ((), ()), {})
        return
    assert changed != base


KEY_SCRIPT = """
import os, sys
sys.path.insert(0, {repo!r})
sys.path.insert(0, os.path.join({repo!r}, "tests"))
import conftest  # the tests' platform and device count
import test_stored_program as t
from dcos_commons_tpu.utils import compile_cache
print("KEY", t._key())
print("OFF", compile_cache.programs_dir())
os.environ["JAX_COMPILATION_CACHE_DIR"] = {cache!r}
compile_cache.enable_compilation_cache()
print("ON", compile_cache.programs_dir())
"""


def test_the_same_inputs_give_the_same_key_in_another_process(tmp_path):
    """A fresh interpreter started from another working directory: no
    path, pid, time or ``id()`` is in the key.  And there the store
    lies under the compile cache's directory, once that is on."""
    cache = str(tmp_path / "cache")
    out = subprocess.run(
        [sys.executable, "-c", KEY_SCRIPT.format(repo=REPO, cache=cache)],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=300,
        env={**{k: v for k, v in os.environ.items()
                if k != "JAX_COMPILATION_CACHE_DIR"},
             "PYTHONHASHSEED": "random"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    said = dict(line.split(" ", 1) for line in out.stdout.splitlines())
    assert said["KEY"] == _key()
    assert said["OFF"] == "None"
    assert said["ON"] == os.path.join(cache, "programs")


def test_what_cannot_be_described_is_not_stored(store):
    """A closed-over value whose text might hold an address: the
    program is compiled and called, and nothing is written."""
    import jax.numpy as jnp

    def _decode(x):
        return x + 1

    program = StoredProgram(_decode, (), dict(what=object()), store)
    assert int(program(jnp.int32(3))) == 4
    assert program._cache_size() == 1
    assert not os.path.exists(store)


# -- jit's contract ------------------------------------------------------


def test_arguments_of_another_type_get_a_program_of_their_own(store):
    import jax.numpy as jnp

    def _decode(tree, x):
        return {"y": tree["w"] * x}

    program = StoredProgram(_decode, (), None, store)
    w = jnp.arange(4, dtype=jnp.float32)
    assert program({"w": w}, np.float32(2))["y"].tolist() == [0, 2, 4, 6]
    assert program({"w": w}, np.float32(3))["y"].tolist() == [0, 3, 6, 9]
    assert program._cache_size() == 1
    # another shape, another dtype, another tree, a weak type: never a
    # TypeError under traffic
    assert program({"w": w[:2]}, np.float32(2))["y"].tolist() == [0, 2]
    assert program({"w": w}, np.int32(2))["y"].dtype == jnp.float32
    assert program({"w": w, "v": w}, np.float32(1))["y"].tolist() == \
        [0, 1, 2, 3]
    assert program({"w": w}, 2.0)["y"].tolist() == [0, 2, 4, 6]
    assert program._cache_size() == 5
    # and back, with no program more
    assert program({"w": w}, np.float32(5))["y"].tolist() == [0, 5, 10, 15]
    assert program({"w": w[:2]}, np.float32(5))["y"].tolist() == [0, 5]
    assert program._cache_size() == 5
    assert len(os.listdir(store)) == stored_program.KEEP
    # the call's own error is the caller's
    with pytest.raises(TypeError):
        program({"w": w}, "two")
    # a second wrapper finds each of the entries that were kept
    again = StoredProgram(_decode, (), None, store)
    assert again({"w": w}, 2.0)["y"].tolist() == [0, 2, 4, 6]


# -- entries that are no good -------------------------------------------


def _add(store, by=1, donate=()):
    import jax.numpy as jnp

    def _decode(cache, x):
        return {"k": cache["k"] + x * by}

    program = StoredProgram(_decode, donate, dict(by=by), store)
    args = ({"k": jnp.zeros(3, jnp.float32)}, np.float32(2))
    return program, args


def _entry(store):
    (name,) = os.listdir(store)
    return os.path.join(store, name)


def _heard_sources(tmp_path, program, args):
    with Heard(tmp_path) as heard:
        out = program(*args)
    return out["k"].tolist(), [
        event for event, _name in heard.events
        if event in (LOAD_EVENT, STORE_EVENT, COMPILE)
    ]


def _break_truncated(path, store):
    with open(path, "rb") as f:
        data = f.read()
    with open(path, "wb") as f:
        f.write(data[: len(data) // 2])


def _break_other_avals(path, store):
    """Under this key's own text, an executable made for other
    argument types."""
    import jax
    import jax.numpy as jnp

    with open(path, "rb") as f:
        material = pickle.load(f)["material"]
    other = jax.jit(lambda cache, x: {"k": cache["k"] + x * 2}).lower(
        {"k": jnp.zeros(5, jnp.float32)}, np.float32(2)
    ).compile()
    stored_program.write_entry(path, material, other)


def _break_other_donation(path, store):
    import jax
    import jax.numpy as jnp

    with open(path, "rb") as f:
        material = pickle.load(f)["material"]
    other = jax.jit(
        lambda cache, x: {"k": cache["k"] + x * 2}, donate_argnums=(0,)
    ).lower({"k": jnp.zeros(3, jnp.float32)}, np.float32(2)).compile()
    stored_program.write_entry(path, material, other)


def _break_other_key(path, store):
    """Another program's entry under this one's name."""
    other_store = store + "-other"
    program, args = _add(other_store, by=3)
    program(*args)
    os.replace(_entry(other_store), path)


def _break_not_an_entry(path, store):
    with open(path, "wb") as f:
        f.write(pickle.dumps(["something", "else"]))


@pytest.mark.parametrize("damage", [
    _break_truncated, _break_other_avals, _break_other_donation,
    _break_other_key, _break_not_an_entry,
], ids=lambda f: f.__name__[len("_break_"):])
def test_an_entry_that_is_no_good_is_compiled_and_replaced(
        damage, store, tmp_path):
    program, args = _add(store, by=2)
    assert _heard_sources(tmp_path, program, args) == (
        [4, 4, 4], [COMPILE, STORE_EVENT]
    )
    path = _entry(store)
    damage(path, store)
    # served, from a compile, and a whole entry takes its place
    program, args = _add(store, by=2)
    assert _heard_sources(tmp_path, program, args) == (
        [4, 4, 4], [COMPILE, STORE_EVENT]
    )
    assert _entry(store) == path
    program, args = _add(store, by=2)
    assert _heard_sources(tmp_path, program, args) == (
        [4, 4, 4], [LOAD_EVENT]
    )


def test_an_unwritable_store_leaves_the_program_compiled(tmp_path):
    # a FILE where the directory should be: nothing can be made there,
    # whoever runs the tests
    blocked = tmp_path / "cache"
    blocked.write_text("not a directory")
    store = str(blocked / "programs")
    for _start in range(2):
        program, args = _add(store)
        assert _heard_sources(tmp_path, program, args) == (
            [2, 2, 2], [COMPILE]
        )
        assert program._cache_size() == 1
    assert blocked.read_text() == "not a directory"


@pytest.mark.parametrize("reserializes", [False, True])
def test_an_executable_the_compile_cache_served_is_stored_only_where_sound(
        reserializes, store, tmp_path, monkeypatch):
    """XLA:CPU writes half of an executable that was itself
    deserialized: what the compile cache served is called and not
    stored, except on a backend known to serialize it whole."""
    import jax

    monkeypatch.setattr(
        stored_program, "RESERIALIZES",
        frozenset({"cpu"} if reserializes else ()),
    )
    program, args = _add(store)
    lower = program._jit.lower

    class CacheServed:
        def lower(self, *args):
            lowered = lower(*args)
            jax.monitoring.record_event_duration_secs(
                stored_program._CACHE_READ_EVENT, 0.01
            )
            return lowered

    program._jit = CacheServed()
    assert _heard_sources(tmp_path, program, args) == (
        [2, 2, 2], [COMPILE] + [STORE_EVENT] * reserializes
    )
    assert os.path.exists(store) == reserializes


def _store_in_a_process(store, gate, out):
    import jax  # noqa: F401 — the child's own backend

    program, args = _add(store, by=5)
    gate.wait(60)
    out.put(program(*args)["k"].tolist())


def test_two_processes_storing_one_key_leave_one_whole_entry(
        store, tmp_path):
    spawn = multiprocessing.get_context("spawn")
    gate, out = spawn.Event(), spawn.Queue()
    workers = [
        spawn.Process(target=_store_in_a_process, args=(store, gate, out))
        for _ in range(2)
    ]
    for worker in workers:
        worker.start()
    gate.set()
    served = [out.get(timeout=240) for _ in workers]
    for worker in workers:
        worker.join(60)
        assert not worker.is_alive() and worker.exitcode == 0
    assert served == [[10, 10, 10]] * 2
    assert [n for n in os.listdir(store) if n.startswith(".tmp-")] == []
    # the entry that stands is whole: the next start loads it
    program, args = _add(store, by=5)
    with Heard(tmp_path) as heard:
        assert program(*args)["k"].tolist() == [10, 10, 10]
    assert [e for e, _n in heard.events if e in (LOAD_EVENT, COMPILE)] == [
        LOAD_EVENT
    ]


def test_pruning_keeps_the_entries_used_last(tmp_path):
    directory = str(tmp_path)
    now = time.time()

    def touch(name, age_s):
        path = os.path.join(directory, name)
        with open(path, "wb") as f:
            f.write(b"x")
        os.utime(path, (now - age_s, now - age_s))

    for i in range(7):
        touch(f"_decode-{i:040d}.program", age_s=100 * i)
        touch(f"_prefill-{i:040d}.program", age_s=100 * i)
    touch(".tmp-dead", age_s=2 * 3600)   # a writer that died
    touch(".tmp-live", age_s=5)          # one that is writing now
    touch("_decode_other-0.program", age_s=9999)  # another name's
    stored_program.prune(directory, "_decode")
    left = sorted(os.listdir(directory))
    assert [n for n in left if n.startswith("_decode-")] == [
        f"_decode-{i:040d}.program" for i in range(stored_program.KEEP)
    ]
    assert len([n for n in left if n.startswith("_prefill-")]) == 7
    assert ".tmp-dead" not in left and ".tmp-live" in left
    assert "_decode_other-0.program" in left
