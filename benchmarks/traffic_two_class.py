"""Requests of two classes of length in one queue, made from ``--seed`` and a
cell's parameters, beside ``traffic.py``'s one-class mix (whose client, adapter
names and distributions this uses).

The *set* is fixed by the parameters: ``requests`` requests, of which every
class holds ``per_block`` of every ``block``, its prompt lengths the
mid-quantiles of its own uniform range.  The seed orders it: it draws the
order in which the classes stand in a block (which 4 of its 16 places the long
requests take: any of the 1,820 ways), and every block of the set has that
order, so any ``block`` requests in a row, wherever they start, hold
``per_block`` of every class; and it draws which of its class's lengths a
request has (every block holds one length from each ``per_block``-th of the
class's range).  A long prompt is a second of prefill: the share of a window
that is prefill moves with the number of long prompts that fall inside it,
and that number with the order (PERF.md section 6, PR 35).
Answers and adapters are stratified over the whole set as in
``traffic.Requests``; token ids are drawn anew for every request sent.
"""

from __future__ import annotations

import numpy as np

from traffic import (adapter_name, apportion, power_law_weights, stratified_order,
                     uniform_quantiles)


class Requests:
    """``mix``: ``requests``, ``block``, ``classes`` (a list of ``{"name",
    "per_block", "prompt": {lo, hi}}`` whose ``per_block`` add up to
    ``block``), ``answer`` {lo, hi}, ``adapters`` {count, power_a},
    ``stagger_first``.  Request i has the sizes, the class and the adapter of
    item ``i % requests`` of the set and token ids of its own; the first
    ``stagger_first`` ask for a part of their answer, as in
    ``traffic.Requests``."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.seed, self.vocab = int(seed), int(vocab)
        n, block = int(mix["requests"]), int(mix["block"])
        classes = mix["classes"]
        if n % block or sum(int(c["per_block"]) for c in classes) != block:
            raise ValueError("the classes' per_block must add up to block, and block divide requests")
        rng = np.random.default_rng([self.seed, 0x5E12])
        blocks = n // block
        # who stands where: one order of a block's places, drawn from the seed
        order = rng.permutation(np.repeat(np.arange(len(classes)),
                                          [int(c["per_block"]) for c in classes]))
        self.kinds = np.tile(order, blocks).astype(np.int64)
        self.prompts = np.zeros(n, np.int64)
        for ci, c in enumerate(classes):
            k = int(c["per_block"])
            sizes = uniform_quantiles(int(c["prompt"]["lo"]), int(c["prompt"]["hi"]), k * blocks)
            # in request order, k a block: every block holds one size from
            # each k-th of the class's range
            self.prompts[self.kinds == ci] = stratified_order(sizes, k, rng)
        self.names = [str(c["name"]) for c in classes]
        a, ad = mix["answer"], mix["adapters"]
        self.answers = stratified_order(uniform_quantiles(a["lo"], a["hi"], n), block, rng)
        who = apportion(power_law_weights(int(ad["count"]), float(ad["power_a"])), n)
        self.who = stratified_order(who, block, rng)
        k = int(mix.get("stagger_first", 0))
        self.part = (rng.permutation(k) + 0.5) / k if k else np.ones(0)

    def __getitem__(self, i: int) -> dict:
        j = i % len(self.prompts)
        rng = np.random.default_rng([self.seed, 0x70C5, int(i)])
        ids = rng.integers(1, self.vocab, size=int(self.prompts[j]))
        answer = int(self.answers[j])
        if i < len(self.part):
            answer = max(int(np.ceil(answer * self.part[i])), 1)
        return {"idx": int(i), "prompt_ids": [int(t) for t in ids], "max_tokens": answer,
                "adapter": adapter_name(int(self.who[j])), "class": self.names[int(self.kinds[j])]}
