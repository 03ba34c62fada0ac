"""sparse_kept_share.serve: ``LM/attn_selected_tokens`` over
``LM/attn_context_tokens``, percent."""
from benchmark.readers import counters


def read(record, metric):
    return counters.share("LM/attn_selected_tokens",
                          "LM/attn_context_tokens")
