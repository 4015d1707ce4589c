"""``sdar_block_attend_roofline``: the least time one chip could take for the operations and
bytes under ``core_block_attend`` (``harness/flops_sdar.block_attend_work``: every pass's rows
against the ring rows a query may see and the block; the ring rows read once a rollout pass and
twice in the learner; no weight) over the traced time under that scope, per dispatch, mean over
chips."""

from benchmark.harness import flops_sdar
from benchmark.readers import _kimilinear, _sdar


def read(record):
    step = _sdar.traced_step(record)
    if step is None:
        return None

    def work(chips):
        return flops_sdar.block_attend_work(
            record["run_config"], record["lanes"] // chips, record["opp_lanes"] // chips, record["rollout_len"], step,
        )

    return _kimilinear.scope_roofline(record, "sdar_block_attend_roofline", "core_block_attend", work)
