"""Operation counts at shapes worked out by hand."""
from bench import flops


def test_ic3net_update_dense():
    c = {"hidden": 128, "flgw_groups": 1, "env_size": 5, "vision": 0,
         "n_actions": 5, "n_agents": 3, "max_steps": 20}
    obs = 2 * 5 + 1 + 1
    per_step = 2 * (obs * 128 + 2 * 128 * 512 + 128 * 128 + 128 * 5 + 128 * 3)
    assert flops.ic3net_update(c, batch=2) == 3 * per_step * 3 * 20 * 2


def test_ic3net_update_counts_the_grouped_share():
    c = {"hidden": 128, "flgw_groups": 4, "env_size": 5, "vision": 1,
         "n_actions": 5, "n_agents": 8, "max_steps": 20}
    obs = 2 * 5 + 9 + 1
    grouped = obs * 128 + 2 * 128 * 512 + 128 * 128 + 128 * 5
    per_step = 2 * (grouped / 4 + 128 * 3)
    assert flops.ic3net_update(c, batch=2) == 3 * per_step * 8 * 20 * 2
