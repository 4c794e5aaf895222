"""One module an architecture, found by the configuration's ``model_type``.

``configs/<config>.json`` says ``"model_type": "<t>"`` (the published
``config.json`` key), and ``archs/<t>.py`` holds everything that is that
architecture's own:

- ``leaf_specs(cfg)``: ``[(name, shape)]`` of the weights, in the order the
  model is written down;
- ``forward(cfg, leaves, tokens, einsum)``: logits ``(B, T, V)`` of
  ``tokens`` ``(B, T)`` in plain ``jax.numpy``, every matrix product through
  ``einsum(spec, a, b)`` (``reference.py`` hands over float32 at ``highest``
  or the fp8 control);
- ``param_count(cfg)`` and ``train_flops_per_token(cfg, seq)``: the count,
  from shapes alone; an architecture whose work a token depends on routing
  states its rule there;
- ``build(cfg, ctx)``: the program's gluon network, initialised on ``ctx``
  with every shape resolved, whose ``collect_params()`` come in
  ``leaf_specs`` order.  The only function that imports ``mxnet_tpu``, and
  it does so inside its body: the module itself imports nothing of the
  program.

``reference.py`` (shapes and logits), ``flops.py`` (the count) and the
drivers (the network) all ask ``of(cfg)``.  A ``model_type`` without a
module is an error that names the file looked for, never a default.
"""
from __future__ import annotations

import functools
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


@functools.lru_cache(maxsize=None)
def load(model_type):
    """``archs/<model_type>.py`` as a module; names may hold dots."""
    path = os.path.join(HERE, model_type + ".py")
    if not os.path.isfile(path):
        raise LookupError("no architecture module for model_type %r: "
                          "looked for %s" % (model_type, path))
    spec = importlib.util.spec_from_file_location(
        "chipbench_archs_" + model_type.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def of(cfg):
    """The module of ``cfg``'s architecture."""
    if not cfg.get("model_type"):
        raise LookupError("the configuration names no model_type, so no "
                          "file under %s is its architecture" % HERE)
    return load(cfg["model_type"])
