"""A cell of BENCHMARK.json cut to a size the CPU test runs hold."""

import run


def tiny_plan(cell: str, scale: int = 9) -> dict:
    """The cell's plan at ``scale``."""
    plan = run.cell_plan(cell)
    plan["config"]["scale"] = scale
    return plan
