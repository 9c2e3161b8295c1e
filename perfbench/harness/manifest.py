"""Finds what a cell is made of, by the names in ``BENCHMARK.json``.

    configs[*].file                         a configuration's sizes
    <bench>/traffic/<traffic>.json          a traffic mix's parameters
    <bench>/cells/<workload>.json           what belongs to one cell
                                            (its rate); optional
    <bench>/end_to_end/<metric>.py          one reader a quantity,
    <bench>/layer_metrics/<metric>.py       ``read(run) -> number|None``;
                                            ``<quantity>.<suffix>``, a
                                            quantity split by the
                                            end-to-end metric it moves,
                                            is read by ``<quantity>.py``
                                            where it has no file of its
                                            own
    <bench>/peaks.json                      peaks by ``device_kind``
    <bench>/families/<family>/              what belongs to one
                                            architecture family, found
                                            by the ``"family"`` key of a
                                            configuration's file
                                            (``family_of``: the
                                            ``families/`` beside the
                                            folder that holds the file);
                                            four files, each with its
                                            hook:
        program_env.py    ``program_env(model, config_path) -> {env
                          name: value}``: the env that makes the program
                          build this configuration (``TASKCFG_ALL_``
                          prefix where the task needs it; the path is
                          for a program that is sized by a file).
                          Raises ``ValueError`` for a configuration the
                          program cannot build.  ``run.py`` merges the
                          mix's ``sizing_env`` over it
        weight_specs.py   ``weight_specs(model) -> [(path, shape, kind,
                          scale, dtype)]``, the tree that
                          ``harness/weights.py`` builds from the seed
        reference.py      ``logits(model, weights, tokens, rows=None,
                          lower=None, margins=False)``: the family's
                          plain reference, float32 logits at ``rows``;
                          ``lower="int8"`` its control; with ``margins``
                          also each position's steadiness margin as the
                          family means it (infinite where nothing is
                          routed)
        needs.py          ``decode_tick(model, live_rows, live_tokens)``
                          and ``prefill_chunk(model, chunk_tokens,
                          context_tokens)`` -> ``{"bytes", "flops",
                          ...}``, what the call needs of the chip

``<bench>`` is the first of ``paths``.  A later PR adds a
configuration, a cell, a traffic mix, a family or a per-layer metric as
NEW files and as entries APPENDED to their lists in ``BENCHMARK.json``;
nothing here names one.  It appends its new cell's name to the
``workloads`` of every metric that the cell reports, and edits nothing
else that is there.  An entry put into the middle of a list reads to
the driver as an edit of the entry whose place it takes, and the PR is
refused.  The tests hold the lists open at their ends
(``tests/bench/toyroot.py appended``: every entry test runs again on a
root with a fourth configuration, cell and metric appended), so find an
entry by its name, never by its place or a list's length.

The one key every configuration's file keeps, whatever its family, is
``vocab_size``: the rows of the vocabulary held here (traffic draws its
ids from it, answers are held to it).  All else in the file is read by
its family's four files alone.  They are loaded in the harness's own
process too, which never touches JAX: import it inside the functions,
as the harness's files do.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
from types import SimpleNamespace

# file -> the functions it must define
FAMILY_HOOKS = {
    "program_env.py": ("program_env",),
    "weight_specs.py": ("weight_specs",),
    "reference.py": ("logits",),
    "needs.py": ("decode_tick", "prefill_chunk"),
}


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def families_present(parent: str) -> str:
    present = sorted(
        d for d in (os.listdir(parent) if os.path.isdir(parent) else [])
        if os.path.isdir(os.path.join(parent, d)) and not d.startswith("_")
    )
    return "families present: " + (", ".join(present) or "none")


def load_family(directory: str) -> SimpleNamespace:
    """The four hooks of the family in ``directory``: ``program_env``
    and ``weight_specs`` (functions), ``reference`` and ``needs``
    (modules), with its ``name`` and ``directory``.  A directory, a file
    or a function that is missing stops with a message that names it and
    the families present."""
    directory = os.path.abspath(directory)
    parent, name = os.path.split(directory)

    def stop(what: str):
        raise SystemExit(
            f"perfbench: family {name!r}: {what}; a family is a directory "
            f"under {parent} with {', '.join(FAMILY_HOOKS)} "
            f"(harness/manifest.py); {families_present(parent)}"
        )

    if not os.path.isdir(directory):
        stop(f"no directory {directory}")
    modules = {}
    for file, functions in FAMILY_HOOKS.items():
        path = os.path.join(directory, file)
        if not os.path.isfile(path):
            stop(f"no file {path}")
        stem = file[:-len(".py")]
        modules[stem] = _load(f"perfbench_family_{name}_{stem}", path)
        for function in functions:
            if not callable(getattr(modules[stem], function, None)):
                stop(f"{path} defines no function {function}")
    return SimpleNamespace(
        name=name, directory=directory,
        program_env=modules["program_env"].program_env,
        weight_specs=modules["weight_specs"].weight_specs,
        reference=modules["reference"], needs=modules["needs"],
    )


@functools.lru_cache(maxsize=None)
def family_of(config_file: str) -> SimpleNamespace:
    """The family of the configuration in ``config_file``, as the
    file's ``"family"`` key names it: ``families/<family>/`` beside the
    folder that holds the file (``load_family``).  The file's path is
    all that the worker entry, the check, the control and the readers
    need to reach it."""
    config_file = os.path.abspath(config_file)
    with open(config_file) as f:
        family = json.load(f).get("family")
    families = os.path.join(
        os.path.dirname(os.path.dirname(config_file)), "families"
    )
    if not isinstance(family, str) or not family:
        raise SystemExit(
            f"perfbench: {config_file} states no \"family\"; "
            f"{families_present(families)}"
        )
    return load_family(os.path.join(families, family))


class Manifest:
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.data = json.load(f)
        self.bench = os.path.join(self.root, self.data["paths"][0])

    def _json(self, *parts):
        with open(os.path.join(self.bench, *parts)) as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        for cell in self.data["workloads"]:
            if cell["name"] == name:
                return cell
        raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json")

    def config_path(self, name: str) -> str:
        for config in self.data["configs"]:
            if config["name"] == name:
                return os.path.join(self.root, config["file"])
        raise SystemExit(f"perfbench: no config {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        with open(self.config_path(name)) as f:
            return json.load(f)

    def family(self, config: str) -> SimpleNamespace:
        """The family of configuration ``config`` (``family_of``)."""
        return family_of(self.config_path(config))

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name + ".json")

    def cell_params(self, name: str) -> dict:
        try:
            return self._json("cells", name + ".json")
        except FileNotFoundError:
            return {}

    def peaks(self) -> dict:
        return self._json("peaks.json")

    def metrics(self, kind: str, cell: str) -> list:
        """The metrics of ``kind`` this cell reports: those that list it
        under ``workloads``, and those that list nothing."""
        return [
            m for m in self.data[kind]
            if cell in m.get("workloads", [cell])
        ]

    def reader(self, kind: str, name: str):
        folder = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}[kind]
        path = os.path.join(self.bench, folder, name + ".py")
        if not os.path.exists(path) and "." in name:
            path = os.path.join(
                self.bench, folder, name.rsplit(".", 1)[0] + ".py"
            )
        return _load(
            "perfbench_reader_" + name.replace(".", "_").replace("-", "_"), path
        ).read
