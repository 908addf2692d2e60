from repro.core.sparsity import (topk_mask, topk_mask_by_count, sparsify,
                                 sparsify_by_count, threshold_exact,
                                 threshold_histogram)
from repro.core.selectors import (Selector, SelectorLike, register_selector,
                                  registered_selectors, resolve_selector)
from repro.core.strategies import (Strategy, StrategySpec, RoundPlan,
                                   UploadRule, PlanContext, register_strategy,
                                   registered_kinds, resolve)
from repro.core.transport import (Message, Pipeline, MaskSparsify,
                                  TopKSparsify, Quantize, LowRankCompress,
                                  register_stage, registered_stages,
                                  download_pipeline, upload_pipeline,
                                  wire_format)
from repro.core.fedround import FlatMeta, federated_round, make_round_fn, init_server
from repro.core.comm import CommLedger, coded_message_bytes

__all__ = ["topk_mask", "topk_mask_by_count", "sparsify", "sparsify_by_count",
           "threshold_exact", "threshold_histogram",
           "Selector", "SelectorLike", "register_selector",
           "registered_selectors", "resolve_selector",
           "Strategy", "StrategySpec", "RoundPlan", "UploadRule",
           "PlanContext", "register_strategy", "registered_kinds", "resolve",
           "Message", "Pipeline", "MaskSparsify", "TopKSparsify", "Quantize",
           "LowRankCompress", "register_stage", "registered_stages",
           "download_pipeline", "upload_pipeline", "wire_format",
           "FlatMeta", "federated_round", "make_round_fn", "init_server",
           "CommLedger", "coded_message_bytes"]
