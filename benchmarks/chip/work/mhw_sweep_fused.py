"""Work the LDA MHW sweep needs for one call: one position chunk of a sweep.

Counted from shapes, whatever implements the sweep (AliasLDA, paper §3):

* operations: per token, the document-sparse target over all K outcomes
  (multiply the doc count by the word factor, and one running sum to
  sample from it: 2 per outcome) and ``mh_steps`` Metropolis-Hastings
  steps (about 20 each: two proposals, two target ratios, one accept);
  per distinct token type, the fresh word factor
  (n_wk+β)/(n_k+Vβ) over K outcomes (2 per outcome).
* bytes: each distinct token type's table rows read once (n_wk, alias
  probability and index, and the stale dense term: 4 rows of K float32 or
  int32), the topic totals once, and per token its document's n_dk row
  (K float32), its five uniform streams per MH step, its token type, its
  old outcome and its new one (4 bytes each).

One-hot staging matrix products and tile padding are implementation
choices and are not counted.
"""

OUTCOMES_PER_TOPIC = 1   # E = K


def work(*, tokens: int, rows: int, n_topics: int, mh_steps: int
         ) -> tuple[float, float]:
    e = OUTCOMES_PER_TOPIC * n_topics
    ops = tokens * (2 * e + 20 * mh_steps) + rows * 2 * e
    nbytes = (rows * 4 * e * 4 + n_topics * 4
              + tokens * (n_topics * 4 + 5 * mh_steps * 4 + 3 * 4))
    return float(ops), float(nbytes)
