"""Lanes that emitted a block, mean per engine step in the window."""


def read(view):
    if not view.steps:
        return None
    return sum(len(s.blocks) for s in view.steps) / len(view.steps)
