"""Unified process launchers (paper §II.A; AiiDA 1.0 ``aiida.engine.launch``).

The one documented way to launch any process::

    from repro.engine.launch import run, run_get_node, run_get_pk, submit

    results = run(AddWorkChain, a=Int(1), b=Int(2))     # blocking
    results, node = run_get_node(builder)               # blocking, + node
    results, pk = run_get_pk(AddWorkChain, a=1, b=2)    # blocking, + pk
    handle = submit(builder)                            # non-blocking

Every launcher accepts either ``(ProcessClass, **inputs)`` or a
:class:`~repro.core.builder.ProcessBuilder` (keyword arguments override
builder values). ``run*`` drive the process to completion on the default
runner's loop; ``submit`` schedules it — on a distributed runner (daemon
worker) the process ships through the durable task queue to the worker
pool, otherwise it runs as a task on the local runner's loop.

``Runner.run``/``Runner.submit`` remain the underlying mechanism; use them
directly only when driving an explicit, non-default runner.
"""

from __future__ import annotations

from collections import namedtuple
from typing import Any, Mapping

from repro.core.builder import expand_launch_target
from repro.core.process import Process

ResultAndNode = namedtuple("ResultAndNode", ["results", "node"])
ResultAndPk = namedtuple("ResultAndPk", ["results", "pk"])


def _default_runner():
    from repro.engine.runner import default_runner
    return default_runner()


def _merge(inputs, kwargs) -> dict[str, Any]:
    """Combine the positional inputs dict and keyword inputs (keywords
    win); both override-styles then flow through the same builder-merge
    semantics of ``expand_launch_target``."""
    overrides = dict(inputs or {})
    overrides.update(kwargs)
    return overrides


def run(process, inputs: Mapping[str, Any] | None = None, *,
        runner=None, **kwargs) -> dict[str, Any]:
    """Run a process to completion, blocking; returns its outputs."""
    return run_get_node(process, inputs, runner=runner, **kwargs).results


def run_get_node(process, inputs: Mapping[str, Any] | None = None, *,
                 runner=None, **kwargs) -> ResultAndNode:
    """Run a process to completion, blocking; returns ``(outputs,
    process)`` — the process object doubles as the provenance node view
    (``.pk``, ``.exit_code``, ``.is_finished_ok``)."""
    # the runner expands the target inside its process.create span
    outputs, node = (runner or _default_runner()).run(
        process, _merge(inputs, kwargs))
    return ResultAndNode(outputs, node)


def run_get_pk(process, inputs: Mapping[str, Any] | None = None, *,
               runner=None, **kwargs) -> ResultAndPk:
    """Run a process to completion, blocking; returns ``(outputs, pk)``."""
    results, node = run_get_node(process, inputs, runner=runner, **kwargs)
    return ResultAndPk(results, node.pk)


def submit(process, inputs: Mapping[str, Any] | None = None, *,
           runner=None, **kwargs):
    """Schedule a process without waiting. Returns a handle with ``.pk``:
    a ``ProcessHandle`` on a local runner, a ``QueuedHandle`` when the
    runner is distributed and the process was shipped to the daemon's
    task queue (paper §III.C.a)."""
    process_class, merged = expand_launch_target(process,
                                                 _merge(inputs, kwargs))
    runner = runner or _default_runner()
    return runner.submit(process_class, inputs=merged)


def instantiate(process, inputs: Mapping[str, Any] | None = None, *,
                runner=None, **kwargs) -> Process:
    """Construct (but do not schedule) a process: node + input links +
    initial checkpoint are created, so the pk can be shipped anywhere."""
    process_class, merged = expand_launch_target(process,
                                                 _merge(inputs, kwargs))
    return process_class(inputs=merged, runner=runner or _default_runner())
