"""The utterance corpus: the five commands of ``bench.py`` as templates
with slot fillers. ``texts(n)`` is a fixed list (no seed): every seed sends
the SAME set, in another order, so the work a run does does not depend on
the seed (with random weights, how long a plan gets is an accident of the
text)."""

from __future__ import annotations

TEMPLATES = [
    "search for {item}",
    "sort these by price from {order}",
    "open the {ordinal} result and take a screenshot",
    "filter results under {amount} dollars",
    "search for {item} then open the {ordinal} result and scroll down",
]
FILLERS = {
    "item": ["wireless headphones", "red running shoes", "a standing desk",
             "usb c cables", "noise cancelling earbuds for long flights",
             "a waterproof hiking jacket in dark green", "coffee", "4k monitors", "a used road bike", "blue light glasses"],
    "order": ["low to high", "high to low", "newest to oldest", "best rated to worst"],
    "ordinal": ["first", "second", "third", "fourth", "last"],
    "amount": ["twenty", "fifty", "one hundred", "two hundred and fifty", "thirty five", "nine"],
}


def texts(n: int) -> list[str]:
    """First ``n`` texts of a fixed enumeration: every template's fillings
    (in the order ``itertools.product`` gives them), the templates taken
    round-robin."""
    import itertools
    import string

    per_tpl = []
    for tpl in TEMPLATES:
        slots = [f for _, f, _, _ in string.Formatter().parse(tpl) if f]
        per_tpl.append([tpl.format(**dict(zip(slots, combo)))
                        for combo in itertools.product(*(FILLERS[s] for s in slots))])
    out = [t for row in itertools.zip_longest(*per_tpl) for t in row if t is not None]
    if len(out) < n:
        raise ValueError(f"the corpus holds {len(out)} distinct texts, fewer than {n}")
    return out[:n]


def seeded_cycle(items: list, seed: int):
    """Endless iterator: whole permutations of ``items``, each drawn from
    the seed — any long prefix is balanced over the set."""
    import random

    rng = random.Random(seed)
    while True:
        order = list(items)
        rng.shuffle(order)
        yield from order
