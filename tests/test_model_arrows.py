"""The arrows of the model layer (ISSUE 47): a model's file imports ``ops/``
and ``models/cache.py`` and no sibling model, ``ops/`` imports nothing of
``models/``, and the counters a routed family's step hands back are declared by
the one function of ``ops/moe.py``."""

import ast
import os

import jax.numpy as jnp
import pytest

from accelerate_tpu.models import deepseek_v3, lfm2, sdar_moe, smallthinker
from accelerate_tpu.ops import moe

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "accelerate_tpu")


def _imports(path):
    """``[(level, module, names)]`` of every import statement of a file,
    those inside functions included."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            out.append((node.level, node.module or "", [a.name for a in node.names]))
        elif isinstance(node, ast.Import):
            out.extend((0, a.name, []) for a in node.names)
    return out


def _modules(folder):
    return sorted(f for f in os.listdir(os.path.join(PACKAGE, folder))
                  if f.endswith(".py") and f != "__init__.py")


def test_no_model_module_imports_a_sibling_and_ops_imports_no_model():
    siblings = {f[:-3] for f in _modules("models")} - {"cache"}
    reached = []
    for f in _modules("models"):
        for level, module, names in _imports(os.path.join(PACKAGE, "models", f)):
            head = module.split(".")[0]
            if level == 1 and (head in siblings or (not module and siblings & set(names))):
                reached.append(f"models/{f}: from .{module} import {', '.join(names)}")
            if level == 0 and module.startswith("accelerate_tpu.models.") \
                    and module.split(".")[2] in siblings:
                reached.append(f"models/{f}: {module}")
    for f in _modules("ops"):
        for level, module, names in _imports(os.path.join(PACKAGE, "ops", f)):
            from_models = module.split(".")[0] == "models" or (not module and "models" in names)
            if (level == 2 and from_models) \
                    or (level == 0 and module.startswith("accelerate_tpu.models")):
                reached.append(f"ops/{f}: {'.' * level}{module}")
    assert reached == []


@pytest.mark.parametrize("family, config, layers, experts, extra", [
    (lfm2, lfm2.Lfm2MoeConfig, "n_moe", "num_experts", ()),
    (sdar_moe, sdar_moe.SdarMoeConfig, "num_hidden_layers", "num_experts", ()),
    (smallthinker, smallthinker.SmallThinkerConfig, "num_hidden_layers",
     "moe_num_primary_experts", ()),
    (deepseek_v3, deepseek_v3.DeepseekV3Config, "n_moe", "n_routed_experts",
     ("moe_pairs_elsewhere_total",)),
], ids=["lfm2", "sdar", "smallthinker", "deepseek"])
def test_a_routed_familys_counters_are_the_one_functions(family, config, layers, experts, extra):
    c = config.tiny()
    layers, experts = getattr(c, layers), getattr(c, experts)
    shapes = family.step_counter_shapes(c)
    assert shapes == moe.step_counter_shapes(layers, experts, extra=extra)
    assert list(shapes) == ["moe_expert_pairs", "moe_dispatches_total", "moe_pairs_routed_total",
                            "moe_experts_touched_total", "moe_load_max_total", *extra]
    assert shapes["moe_expert_pairs"] == (layers, experts)
    assert all(shape == () for name, shape in shapes.items() if name != "moe_expert_pairs")
    # what fills them: one entry a declared name, of the declared shape
    filled = moe.step_counters([jnp.arange(experts)] * layers)
    assert {k: v.shape for k, v in filled.items()} == {
        k: v for k, v in shapes.items() if k not in extra}
