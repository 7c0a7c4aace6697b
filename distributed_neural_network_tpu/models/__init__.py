"""Subpackage: models."""

import importlib

# The model modules a configuration file's "family" can name
# (`lm_train.py --model-config`); GPT-2's block (`transformer`) is built from
# the trainer's own flags. A module is imported when its family is asked for.
# (`pangu_ultra_moe`, `lfm2_moe` and `mimo_v2` are served, `python -m ...serve
# --model-config`, and not trained: they have no `apply_hidden`, and the
# trainer says so.)
FAMILIES = {"nemotron_h": "nemotron_h", "pangu_ultra_moe": "pangu_ultra_moe",
            "lfm2_moe": "lfm2_moe", "mimo_v2": "mimo_v2"}


def family_module(family: str):
    """The module that runs `family` (KeyError where there is none)."""
    return importlib.import_module(f"{__name__}.{FAMILIES[family]}")
