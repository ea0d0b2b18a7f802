"""Model families: what the harness knows of a configuration's block.

A configuration file (``bench/configs/<name>.json``) names its ``family``;
``bench/models/<family>.py`` builds from it the program's ``ModelConfig``
(``model_config``), its weights made from the seed on the device
(``program_params``) and its engine settings (``perf``), gives the leaves
the plain reference rebuilds (``LAYER_LEAVES``, ``global_leaves``,
``layer_weights``, ``global_weights``), and counts the FLOPs and bytes the
metrics divide by (``dims``, ``token_flops``, ``flash_decode_cost``). A
family module imports the program only inside the functions that build it,
so that a reference can import its leaves and still import nothing of the
program.
"""
from __future__ import annotations

import importlib


def family(m: dict):
    """The family module that configuration ``m`` names."""
    name = m.get("family")
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(f"configuration {m.get('name')!r} names no model "
                         f"family (key 'family': {name!r})")
    module = f"{__name__}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ValueError(f"configuration {m.get('name')!r} names the unknown "
                         f"model family {name!r} (no bench/models/{name}.py)"
                         ) from None
