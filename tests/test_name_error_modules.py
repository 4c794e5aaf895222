"""mx.name (NameManager/Prefix) and mx.error / mx.executor parity.

Reference: ``python/mxnet/name.py`` (auto-naming manager stack),
``python/mxnet/error.py`` (registered error hierarchy),
``python/mxnet/executor.py`` (Executor exposure).
"""
import numpy as np
import pytest

import mxnet_tpu as mx


def test_default_auto_naming_counts_per_hint():
    a = mx.sym.var("x")
    s1 = mx.sym.FullyConnected(a, num_hidden=4)
    s2 = mx.sym.FullyConnected(a, num_hidden=4)
    n1, n2 = s1.name, s2.name
    assert n1.startswith("fullyconnected") and n2.startswith("fullyconnected")
    assert n1 != n2


def test_prefix_manager_scopes_names():
    a = mx.sym.var("x")
    with mx.name.Prefix("net0_"):
        s = mx.sym.FullyConnected(a, num_hidden=4)
    assert s.name.startswith("net0_fullyconnected")
    # scope restored: no prefix outside
    s2 = mx.sym.FullyConnected(a, num_hidden=4)
    assert not s2.name.startswith("net0_")


def test_custom_name_manager_nesting():
    class Upper(mx.name.NameManager):
        def get(self, name, hint):
            return super().get(name, hint).upper()

    a = mx.sym.var("x")
    with Upper():
        s = mx.sym.relu(a)
        with mx.name.Prefix("in_"):
            t = mx.sym.relu(a)
        u = mx.sym.relu(a)
    assert s.name.isupper()
    assert t.name.startswith("in_")
    assert u.name.isupper()
    # explicit names always win
    v = mx.sym.relu(a, name="myrelu")
    assert v.name == "myrelu"


def test_error_registry_and_internal_error():
    assert mx.error.get_error_class("ValueError") is ValueError
    assert mx.error.get_error_class("MXNetError") is mx.MXNetError
    assert mx.error.get_error_class("nope") is mx.MXNetError
    with pytest.raises(mx.error.InternalError, match="hint"):
        raise mx.error.InternalError("boom")

    @mx.error.register
    class CustomError(mx.MXNetError):
        pass

    assert mx.error.get_error_class("CustomError") is CustomError


def test_executor_module_reexports():
    from mxnet_tpu.executor import Executor
    from mxnet_tpu.symbol.executor import Executor as E2

    assert Executor is E2
    x = mx.sym.var("x")
    y = mx.sym.relu(x)
    ex = y.bind(mx.cpu(), {"x": mx.nd.array(np.array([-1.0, 2.0],
                                                     np.float32))})
    assert isinstance(ex, Executor)
    out = ex.forward()[0].asnumpy()
    assert np.allclose(out, [0.0, 2.0])


def test_prefix_applies_to_explicit_names():
    # reference semantics: the manager sees user-supplied names too
    a = mx.sym.var("x")
    with mx.name.Prefix("scoped_"):
        s = mx.sym.relu(a, name="myrelu")
    assert s.name == "scoped_myrelu"
