"""Shared helpers for the test suite."""

import importlib.util
import os
import uuid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def clean_env(*, cpu_pin: bool = True) -> dict:
    """Subprocess environment for worker processes: repo importable, the
    pytest process's 8-device XLA forcing dropped (workers set their own),
    and — unless ``cpu_pin=False`` — pinned to the CPU (a plain ``python``
    child would otherwise claim the chip where there is one)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if cpu_pin:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def uniq(tag: str) -> str:
    """Collision-free resource name (shm segments, window names) so
    parallel or crashed test runs cannot alias each other's state."""
    return f"{tag}_{uuid.uuid4().hex[:8]}"


def load_script(relpath: str):
    """Import a repo script (chip_smoke.py, benchmarks/*.py) as a module — these
    live outside the package, so the ordinary import system can't see
    them.  One canonical loader, not one copy per test file."""
    path = os.path.join(REPO, relpath)
    name = os.path.splitext(os.path.basename(relpath))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def walk_jaxpr(jaxpr, above=""):
    """Every equation of ``jaxpr`` with the name stacks of the equations
    enclosing it, nested jaxprs (switch and cond branches, a ``shard_map``
    body) included."""
    for eqn in jaxpr.eqns:
        stack = f"{above}/{eqn.source_info.name_stack}"
        yield eqn, stack
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from walk_jaxpr(sub, stack)
