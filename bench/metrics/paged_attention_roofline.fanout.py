"""paged_attention_roofline.fanout: the paged attention kernel's share of its roofline."""
from bench.readers import attention_calls, roofline


def read(run):
    return roofline(run, "paged_attention", attention_calls(run))
