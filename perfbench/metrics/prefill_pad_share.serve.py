"""Share of the prefill positions computed in the traced window that are
bucket padding: 1 - prompt tokens / bucket tokens (%)."""
from lib import phases


def read(run):
    p = phases.for_run(run)
    if p is None or not p.bucket_tokens:
        return None
    return (1 - p.prompt_tokens / p.bucket_tokens) * 100
