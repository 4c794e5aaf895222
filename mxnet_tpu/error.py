"""Structured error classes.

Parity: ``python/mxnet/error.py`` — the reference maps C-ABI error
prefixes ("ValueError: ...") onto registered Python exception types via
``register_error``.  There is no C ABI here (errors are ordinary Python
exceptions end-to-end), so ``register`` keeps the registry purely for
API parity: code that registers custom error types and code that looks
them up by name keeps working, and the standard hierarchy
(``InternalError``, ``NotImplementedForTPU`` alias, builtin
ValueError/TypeError/AttributeError/IndexError) is pre-registered.
"""
from __future__ import annotations

from .base import MXNetError, NotSupportedForTPU

__all__ = ["MXNetError", "InternalError", "register"]

_ERROR_REGISTRY = {}


def register(name_or_cls, cls=None):
    """Register an error class under a name (parity:
    ``base.register_error``).  Usable as a decorator::

        @mx.error.register
        class MyError(mx.MXNetError): ...

    or with an explicit name: ``register("ValueError", ValueError)``.
    """
    if cls is not None:
        _ERROR_REGISTRY[str(name_or_cls)] = cls
        return cls
    _ERROR_REGISTRY[name_or_cls.__name__] = name_or_cls
    return name_or_cls


def get_error_class(name, default=MXNetError):
    """Look up a registered error class by name."""
    return _ERROR_REGISTRY.get(name, default)


@register
class InternalError(MXNetError):
    """Internal error in the runtime (parity: error.py:31).  The hint
    suffix mirrors the reference's convention of pointing users at the
    issue tracker for errors that indicate a framework bug."""

    def __init__(self, msg):
        if "hint:" not in msg:
            msg += ("\nhint: you hit an internal error; please report it "
                    "with the full traceback")
        super().__init__(msg)


register("MXNetError", MXNetError)
register("NotSupportedForTPU", NotSupportedForTPU)
register("ValueError", ValueError)
register("TypeError", TypeError)
register("AttributeError", AttributeError)
register("IndexError", IndexError)
