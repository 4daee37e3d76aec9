"""The op-path parser under the name it first had: ``scope_of`` and
``hlo_op_names`` live in ``trace.py``, which reduces a traced run's
device time by named scope; ``tests/test_named_scopes.py`` loads them
from here."""
import harness as H

_T = H.load_module("trace")     # not the stdlib's trace
scope_of = _T.scope_of
hlo_op_names = _T.hlo_op_names
