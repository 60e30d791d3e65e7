"""Device time of one run of the program matching ``per_module`` by the part
of the model its operations belong to (``lib/scopes.py``: the trace's events
joined with the ``op_name`` tables the program writes beside the trace at
``stop_profile``).  ``scope`` is a regex searched in an operation's
``op_name``, where a ``jax.named_scope`` ``<family>.<part>`` is one component.
``stat`` = ``ms``: the SELF time of the operations it finds, per run of the
program, mean over runs and chips (as ``op_ms_per_run`` counts runs), ms;
``pct_outside``: the share of the program's self time it does NOT find, in %
(with ``scope`` = every part's name: the time in no scope).  ``None`` when
there is no run or no table, when ``ms`` finds nothing, and when more than
1 % of the program's self time is unresolved (an instruction in no table,
rungs that disagree): a guess is worse than a hole."""

from benchmarks.lib import scopes


def read(ctx, scope, per_module, stat):
    programs = scopes.for_ctx(ctx)
    if programs is None:
        return None
    return scopes.stat(programs, per_module, scope, stat)
