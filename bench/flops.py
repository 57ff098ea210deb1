"""Operations and bytes that the model needs, from shapes alone.

Counts follow what the mathematics requires, not what one implementation
computes: a grouped FLGW layer needs ``2 * rows * M * N / G`` operations
(no padded capacity blocks), and a training step needs the forward pass
once and the backward pass (twice the forward) once, with no
rematerialised recompute.
"""
from __future__ import annotations

TRAIN = 3  # forward + backward (2x forward)


def ic3net_update(c: dict, batch: int) -> float:
    """Operations one IC3Net update needs: every projection of every agent
    at every step of every env, forward and backward."""
    h, g = c["hidden"], max(1, c["flgw_groups"])
    obs = 2 * c["env_size"] + (2 * c["vision"] + 1) ** 2 + 1
    grouped = obs * h + 2 * h * 4 * h + h * h + h * c["n_actions"]
    dense = h * 1 + h * 2
    per_agent_step = 2.0 * (grouped / g + dense)
    return TRAIN * per_agent_step * c["n_agents"] * c["max_steps"] * batch
