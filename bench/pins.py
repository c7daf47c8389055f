"""The planner's per-weight parameters, pinned in each configuration.

    python3 bench/pins.py --config sift128_p2

prints the ``plan`` object that a configuration file holds: for every
table group its center weight id and bucket width, and for every weight
id its group, table count ``beta``, collision threshold ``mu``, radius
base ``r_min`` and level count ``n_levels``.  They depend only on the
weight set and the configuration's p, c, n, gamma_n, tau and v, never on
the corpus, so they are worked out once, with the program's planner, and
written into the configuration.  A run's reference takes them from the
configuration, never from the run's own plan, and the check counts every
pinned value that the run's plan departs from (``plan_mismatches``): a
planner that plans fewer tables or a higher threshold is not correct.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

__all__ = ["plan_mismatches", "plan_pins"]


def plan_pins(config: dict) -> dict:
    """The ``plan`` object of ``config``, from the program's planner."""
    from repro.core.params import PlanConfig
    from repro.core.partition import partition

    from bench import gen

    weights = gen.make_weight_set(config["n_weights"], config["d"],
                                  config["n_subset"], config["n_subrange"],
                                  seed=config["weights_seed"])
    part = partition(weights, PlanConfig(p=config["p"], c=config["c"],
                                         n=config["n"],
                                         gamma_n=config["gamma_n"]),
                     config["value_range"], config["tau"], v=config["v"],
                     v_prime=config["v"])
    members: list = [None] * len(weights)
    for gi, g in enumerate(part.groups):
        # the integer threshold the index serves: reduced, rounded up
        mus = np.maximum(1, np.ceil(g.mus_reduced - 1e-9)).astype(int)
        for slot, wid in enumerate(g.member_ids):
            members[int(wid)] = dict(
                group=gi, beta=int(g.betas[slot]), mu=int(mus[slot]),
                r_min=float(g.r_min_members[slot]),
                n_levels=int(g.n_levels[slot]))
    return dict(groups=[dict(center_id=int(g.center_id), width=float(g.width))
                        for g in part.groups],
                members=members)


def plan_mismatches(pins: dict, plan) -> int:
    """Pinned values that the exported serving ``plan`` departs from."""
    bad = abs(len(plan.groups) - len(pins["groups"]))
    for g, pg in zip(plan.groups, pins["groups"]):
        bad += int(g.center_id) != pg["center_id"]
        bad += float(g.width) != pg["width"]
    seen = set()
    for gi, g in enumerate(plan.groups):
        for slot, wid in enumerate(g.member_ids):
            wid = int(wid)
            seen.add(wid)
            if wid >= len(pins["members"]):
                bad += 1
                continue
            pm = pins["members"][wid]
            bad += sum((pm["group"] != gi,
                        pm["beta"] != int(g.beta_members[slot]),
                        pm["mu"] != int(g.mu_members[slot]),
                        pm["r_min"] != float(g.r_min_members[slot]),
                        pm["n_levels"] != int(g.n_levels_members[slot])))
    return bad + len(set(range(len(pins["members"]))) - seen)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    with open(os.path.join(root, "bench", "configs",
                           f"{args.config}.json")) as fh:
        config = json.load(fh)
    print(json.dumps(plan_pins(config), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
