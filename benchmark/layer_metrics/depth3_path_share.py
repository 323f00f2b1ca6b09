"""Of the paths that ended the last message call alive and without
error in the lanes of a system's ``router`` (the member the
transactions go to), the share whose deepest frame ran three contracts
deep: ``depth3_by_contract`` over ``paths_by_contract`` of the
``harvest`` spans of the highest ``tx``, at the contracts whose name
(``contract_names``) ends in ``__router``. A program whose harvest
names no contracts, or a window without a system, gives nothing to
read. Layer: exploration driver. Moves ``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import spans  # noqa: E402


def read(obs: dict):
    if obs.get("kind") != "campaign":
        return None
    got = [s for s in spans(obs, "harvest")
           if "depth3_by_contract" in s and "contract_names" in s]
    if not got:
        return None
    last = max(s.get("tx", 0) for s in got)
    deep = paths = 0
    for s in got:
        if s.get("tx", 0) != last:
            continue
        for k, name in enumerate(s["contract_names"]):
            if name.endswith("__router"):
                deep += s["depth3_by_contract"][k]
                paths += s["paths_by_contract"][k]
    if paths <= 0:
        return None
    return 100.0 * deep / paths
