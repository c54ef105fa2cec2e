"""Metric ``prefill_token_use`` (prefill engine): the prompt tokens of
the prefill calls wholly inside the traced window, as a share of the
tokens of the pow2 length buckets they were padded to.

Read from the prefill seam's log (``SeamLog.prefills``: each prompt's
length and bucket), the numbers the program's ``serve.prefill`` span
carries as ``true_tokens`` and ``bucket_tokens`` when each call holds
one prompt, as the cells do (``max_prefill_batch`` 1); a call of
several prompts would also pad its batch to a power of two, which this
reader does not count. None without a trace or a prefill in it."""
from typing import Optional


def read(run) -> Optional[float]:
    pre = run.traced_prefills()
    padded = sum(b for p in pre for b in p.buckets)
    if padded <= 0:
        return None
    return 100.0 * sum(n for p in pre for n in p.true_lens) / padded
