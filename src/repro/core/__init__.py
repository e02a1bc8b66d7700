"""The paper's primary contribution: BCRS + OPWA compressed aggregation."""
from repro.core.aggregation import AggregationConfig, aggregate
from repro.core.bcrs import (BCRSSchedule, ClientLink, client_coefficients,
                             comm_time, make_schedule, pod_link_schedule,
                             schedule_crs)
from repro.core.compression import (Compressed, ef_compress, flatten_tree,
                                    from_sparse, k_for_ratio,
                                    quantize_stochastic, randk_compress,
                                    to_sparse, topk_compress,
                                    topk_compress_dynamic)
from repro.core.cost_model import (RoundTime, TimeAccumulator, round_times,
                                   sample_links, uncompressed_round)
from repro.core.opwa import (bcrs_aggregate, opwa_aggregate, opwa_mask,
                             overlap_counts, overlap_histogram)

__all__ = [
    "AggregationConfig", "aggregate", "BCRSSchedule", "ClientLink",
    "client_coefficients", "comm_time", "make_schedule", "pod_link_schedule",
    "schedule_crs", "Compressed", "ef_compress",
    "flatten_tree", "from_sparse", "k_for_ratio", "quantize_stochastic",
    "randk_compress", "to_sparse", "topk_compress", "topk_compress_dynamic",
    "RoundTime",
    "TimeAccumulator", "round_times", "sample_links", "uncompressed_round",
    "bcrs_aggregate", "opwa_aggregate", "opwa_mask", "overlap_counts",
    "overlap_histogram",
]
