"""Loads a benchmark file by path: a metric reader, a reference, a
configuration's family or a traffic generator, each found by a name
in ``BENCHMARK.json`` or in a data file."""

from __future__ import annotations

import functools
import importlib.util
import pathlib
import sys


@functools.lru_cache(maxsize=None)
def load_module(path: pathlib.Path):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    name = "bench_" + "_".join(
        s.replace(".", "_").replace("-", "_")
        for s in (path.parent.name, path.stem))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def family(c: dict):
    """The architecture family of configuration file ``c``: the module
    ``bench/configs/<c["family"]>.py`` beside its reference, which maps
    the file onto the program (``program_config``), scales the drawn
    weights (``leaf_std``) and counts the family's work
    (``prefill_flops``, ``decode_flops``, ``paged_attention_work``,
    ``flash_prefill_work``)."""
    return load_module(pathlib.Path(__file__).resolve().parent / "configs"
                       / f"{c['family']}.py")
