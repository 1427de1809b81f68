"""Closed loop: a fixed number of events outstanding for the whole window.

Each answer collected lets the next event in, as a node draining a buffered
backlog does; the queue never grows past ``outstanding``.
"""
from __future__ import annotations

OPEN_LOOP = False


def outstanding(params: dict) -> int:
    return int(params["outstanding"])
