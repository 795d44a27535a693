"""Donation audit: the engine's step programs must donate cleanly — donation is
the HBM margin that decides the remat policy (earlier rig, not re-measured).

The suite's "Some donated buffers were not usable" warnings come from paths that
donate the GRAD tree into the update program. A grad leaf can alias an output only
where shape, dtype and layout match one (the new compute params), so XLA may
report some unusable for output aliasing — but donation still lets the buffer be
overwritten mid-execution, which is the point (an undonated grad tree holds a full
param-tree of HBM through the update). Such a warning may list gradient-shaped
buffers only: a master, moment or loss-scale buffer there means the update stopped
aliasing its state.
"""

import re
import warnings

import jax
import numpy as np
import pytest

import deepspeed_tpu
from simple_model import SimpleModel, simple_config

HIDDEN = 16


@pytest.mark.parametrize("path,gas", [("two_program", 1), ("two_program", 2),
                                      ("fused_step", 1)])
def test_step_path_donates_cleanly(path, gas):
    """Every step path that remains: no donation warning at all, or one that names
    only buffers shaped like a gradient leaf (the accumulator, reused on purpose)."""
    model = SimpleModel(HIDDEN)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params=simple_config(batch=8 * gas, gradient_accumulation_steps=gas,
                                    fused_step=(path == "fused_step"),
                                    zero_optimization={"stage": 2}))
    assert (engine._run_fused_step is not None) == (path == "fused_step")
    x = np.random.default_rng(0).normal(size=(8, HIDDEN)).astype(np.float32)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for _ in range(2 * gas):            # two optimizer steps
            loss = engine(x, np.tanh(x))
            engine.backward(loss)
            engine.step()
    assert engine.global_steps == 2
    msgs = [str(w.message) for w in caught if "donated" in str(w.message).lower()]
    grad_shapes = {tuple(l.shape) for l in jax.tree_util.tree_leaves(engine.params)}
    for m in msgs:
        named = re.findall(r"\w+\[([\d,\s]*)\]", m)
        assert named, f"a donation warning that names no buffer: {m}"
        for dims in named:
            shape = tuple(int(d) for d in dims.split(",") if d.strip())
            assert shape in grad_shapes, \
                f"{path} gas={gas}: a buffer that is no gradient leaf was not usable: {m}"
    if gas == 1:
        # nothing is accumulated: every donated buffer has an output to alias
        assert not msgs, f"{path} gas={gas} mis-donates: {msgs}"
