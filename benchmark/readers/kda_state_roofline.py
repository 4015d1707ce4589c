"""``kda_state_roofline``: the least time one chip could take for the KDA recurrence's
operations and bytes (``harness/flops_kimilinear.kda_state_work``: each rollout step reads and
writes each lane-layer's float32 state once, the update reads each start state forward and
backward) over the traced time under ``core_kda_state``, per dispatch, mean over chips."""

from benchmark.harness import flops_kimilinear
from benchmark.readers import _kimilinear


def read(record):
    def work(chips):
        return flops_kimilinear.kda_state_work(
            record["run_config"], record["lanes"] // chips, record["opp_lanes"] // chips, record["rollout_len"],
        )

    return _kimilinear.scope_roofline(record, "kda_state_roofline", "core_kda_state", work)
