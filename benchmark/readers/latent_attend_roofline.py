"""``latent_attend_roofline``: the least time one chip could take for the absorbed products
against the latent ring (``harness/flops_kimilinear.latent_attend_work``: of the ring the rows
a query may see, from the lanes' mean position inside the traced dispatches) over the traced
time under ``core_latent_attend``, per dispatch, mean over chips."""

from benchmark.harness import flops_kimilinear
from benchmark.readers import _afmoe, _kimilinear


def read(record):
    position = _afmoe.traced_position(record)
    if position is None:
        return None

    def work(chips):
        return flops_kimilinear.latent_attend_work(
            record["run_config"], record["lanes"] // chips, record["opp_lanes"] // chips,
            record["rollout_len"], position,
        )

    return _kimilinear.scope_roofline(record, "latent_attend_roofline", "core_latent_attend", work)
