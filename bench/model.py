"""A configuration file, the architecture module it names, and its weights.

``load_config`` reads ``bench/configs/<name>.json``.  The file names its
architecture module under ``reference``: ``<reference>.py`` in the same
directory, which ``architecture`` loads.  That module holds everything
that depends on the model (the plain reference, the registry check, the
weight tree, the sizes the readers see and the work a step needs), so a
new architecture is a new module and a new file, and nothing here
changes.  ``registry_config`` stops the run before anything is built
when the program's registry entry does not serve the file's widths.
``make_weights`` draws every parameter on the device in one jitted call
from the seed, through the module, never through the program.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Optional

import jax
import numpy as np

HERE = Path(__file__).resolve().parent


@functools.lru_cache(maxsize=None)
def load_module(path: Path):
    """The Python file at ``path`` as a module, loaded once per process."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str, root: Optional[Path] = None) -> dict:
    """The configuration ``<name>.json`` in ``root``, with the directory
    it came from under ``config_dir``."""
    root = Path(root or HERE / "configs").resolve()
    spec = json.loads((root / f"{name}.json").read_text())
    assert spec["name"] == name, f"{name}.json names {spec['name']!r}"
    spec["config_dir"] = str(root)
    return spec


def architecture(spec: dict):
    """The module the configuration names, from beside its file."""
    return load_module(Path(spec["config_dir"]) / f"{spec['reference']}.py")


def registry_config(spec: dict):
    from repro.configs import get_config
    cfg = get_config(spec["registry"])
    architecture(spec).check_registry(spec, cfg)
    return cfg


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed (the driver's exceed 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    key = jax.random.PRNGKey(0)
    for w in words:
        key = jax.random.fold_in(key, int(w) & 0x7FFFFFFF)
    return key


def make_weights(spec: dict, seed: int):
    """Every weight, drawn on the default device by one jitted call."""
    arch = architecture(spec)
    fn = jax.jit(lambda k: arch.weights(spec, k))
    return jax.block_until_ready(fn(seed_key(seed)))


def serving_config(spec: dict):
    """``ServingConfig`` from every key of the file's ``serving`` group;
    a key the program's ``ServingConfig`` lacks stops the run."""
    from repro.serving import ServingConfig
    known = {f.name for f in dataclasses.fields(ServingConfig)}
    unknown = sorted(set(spec["serving"]) - known)
    if unknown:
        raise SystemExit(f"config {spec['name']}: ServingConfig has no {unknown}")
    return ServingConfig(**spec["serving"])
