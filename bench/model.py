"""A configuration file as the program's model, through its family.

A configuration selects its family module by its ``"family"`` key
(``bench/families/__init__.py`` lists what a family module defines); an
absent key means ``"dense"``.
"""

from __future__ import annotations

import pathlib
from typing import Dict, Tuple

from bench import spec


def family(cfg: dict, root: pathlib.Path = spec.ROOT):
    """The family module of ``cfg``, looked for under ``root`` first; an
    unknown family raises ``FileNotFoundError`` naming the paths."""
    return spec.load_module(
        root, f"bench/families/{cfg.get('family', 'dense')}.py")


def program_config(cfg: dict, root: pathlib.Path = spec.ROOT):
    """The ``repro.models.common.ModelConfig`` that serves ``cfg``."""
    return family(cfg, root).program_config(cfg)


def param_shapes(model_cfg) -> Dict[str, Tuple[int, ...]]:
    """The program's parameter names and shapes for ``model_cfg``."""
    from repro.models import model_zoo
    return {p: tuple(s.shape)
            for p, s in model_zoo.param_table(model_cfg).items()}
