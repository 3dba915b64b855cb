"""The preemption dry run's work, counted from shapes: one visit of a
pod slot of a node per preemption. Whatever implements the dry run, a
share of a roofline for it reads this count."""


def dry_run_slot_visits(preemptions: float, nodes: int, slots: int) -> float:
    """Slot visits of `preemptions` dry runs over `nodes` nodes of
    `slots` pod slots each (the nodes' pod capacity)."""
    return float(preemptions) * nodes * slots
