"""Table generators and join predicates of the paper's three scenarios.

A copy, kept with the benchmark, of the generators in the program's
``data/scenarios.py`` (emails, reviews, ads; paper Table 2), with the
randomness taken from a caller's ``random.Random`` so that every query
of a traffic mix draws fresh tables from its own seed.  The predicates
here decide the ground truth that ``correct`` compares the joins with.
"""

from __future__ import annotations

import dataclasses
import re
import random
from typing import Callable, List, Optional, Set, Tuple

_WORD_RE = re.compile(r"\w+|[^\w\s]")


def word_count(text: str) -> int:
    """Words and punctuation marks (the paper's token counter)."""
    return len(_WORD_RE.findall(text))


@dataclasses.dataclass
class Tables:
    name: str
    r1: List[str]
    r2: List[str]
    condition: str
    predicate: Callable[[str, str], bool]

    def truth(self) -> Set[Tuple[int, int]]:
        return {(i, k) for i, a in enumerate(self.r1)
                for k, b in enumerate(self.r2) if self.predicate(a, b)}

    @property
    def selectivity(self) -> float:
        return len(self.truth()) / (len(self.r1) * len(self.r2))


# ---------------------------------------------------------------- emails

_NAMES = ["Alice", "Bob", "Carol", "David", "Emma",
          "Frank", "Grace", "Henry", "Irene", "Jack"]
_MONTHS = ["January", "February", "March", "April", "May", "June", "July",
           "August", "September", "October", "November", "December"]
_MONTH_NUM = {m: i + 1 for i, m in enumerate(_MONTHS)}
_EMAIL_RE = re.compile(
    r"I first told (?P<name>\w+) about the losses in (?P<month>\w+) "
    r"(?P<year>\d{4})")
_STMT_RE = re.compile(
    r"^(?P<name>\w+): .*first heard about the losses in (?P<month>\w+) "
    r"(?P<year>\d{4})")


def emails_contradict(email: str, statement: str) -> bool:
    me, ms = _EMAIL_RE.search(email), _STMT_RE.search(statement)
    if not (me and ms) or me.group("name") != ms.group("name"):
        return False
    e = (int(me.group("year")), _MONTH_NUM.get(me.group("month"), 0))
    s = (int(ms.group("year")), _MONTH_NUM.get(ms.group("month"), 0))
    return e < s


def emails(rng: random.Random, n1: int = 100, n2: int = 10,
           n_contradictions: int = 10) -> Tables:
    statements = [f"{name}: I swear that I first heard about the losses in "
                  f"February 2022." for name in _NAMES[:n2]]
    early = [("October", 2021), ("November", 2021), ("December", 2021),
             ("January", 2022)]
    late = [("March", 2022), ("April", 2022), ("May", 2022),
            ("June", 2022), ("July", 2022), ("August", 2022)]
    contradict = set(rng.sample(range(n1), min(n_contradictions, n1)))
    rows = []
    for i in range(n1):
        name = _NAMES[rng.randrange(n2)]
        month, year = rng.choice(early if i in contradict else late)
        rows.append(f"I remember that I first told {name} about the losses "
                    f"in {month} {year}.")
    return Tables("emails", rows, statements,
                  "the two texts contradict each other", emails_contradict)


# --------------------------------------------------------------- reviews

_POS = ["brilliant", "moving", "masterful", "delightful", "gripping",
        "superb", "heartfelt", "stunning", "flawless", "memorable"]
_NEG = ["dull", "tedious", "clumsy", "forgettable", "incoherent",
        "lifeless", "grating", "shallow", "bloated", "painful"]
_GENRES = ["drama", "thriller", "comedy", "western", "documentary",
           "musical"]
_SUBJECTS = ["the lead actor", "the screenplay", "the pacing", "the score",
             "the cinematography", "the ending", "the dialogue",
             "the villain"]


def _sentiment(text: str) -> Optional[bool]:
    pos = sum(text.count(w) for w in _POS)
    neg = sum(text.count(w) for w in _NEG)
    return None if pos == neg else pos > neg


def reviews_match(a: str, b: str) -> bool:
    x, y = _sentiment(a), _sentiment(b)
    return x is not None and y is not None and x == y


def _review(rng: random.Random, positive: bool, target: int) -> str:
    lex = _POS if positive else _NEG
    parts = [f"I watched this {rng.choice(_GENRES)} last weekend and I have "
             f"rarely felt this strongly about a film of its kind."]
    verdict = "works wonderfully" if positive else "falls completely flat"
    while word_count(" ".join(parts)) < target - 12:
        parts.append(f"In particular, {rng.choice(_SUBJECTS)} is "
                     f"{rng.choice(lex)} and {verdict}.")
    parts.append("Overall I would happily recommend it to anyone."
                 if positive else "Overall I cannot recommend it to anyone.")
    return " ".join(parts)


def reviews(rng: random.Random, n1: int = 50, n2: int = 50) -> Tables:
    def side(n: int) -> List[str]:
        labels = [True] * (n // 2) + [False] * (n - n // 2)
        rng.shuffle(labels)
        return [_review(rng, lab, rng.randint(92, 106)) for lab in labels]

    r1, r2 = side(n1), side(n2)
    return Tables("reviews", r1, r2,
                  "both reviews are positive or both are negative",
                  reviews_match)


# ------------------------------------------------------------------- ads

_MATERIALS = ["made of solid oak wood", "made of brushed steel",
              "made of tempered glass", "made of reclaimed pine"]
_COLORS = ["painted blue", "painted white", "left natural", "stained dark"]
_AD_RE = re.compile(
    r"(?:Offering|Searching) table that is (?P<mat>made of [\w ]+?|left "
    r"[\w ]+?) and (?P<col>painted \w+|left natural|stained \w+)\.")


def ads_match(ad: str, search: str) -> bool:
    ma, ms = _AD_RE.match(ad), _AD_RE.match(search)
    return bool(ma and ms and ma.group("mat") == ms.group("mat")
                and ma.group("col") == ms.group("col"))


def ads(rng: random.Random, n1: int = 16, n2: int = 16) -> Tables:
    combos = [(m, c) for m in _MATERIALS for c in _COLORS]
    offers = [f"Offering table that is {m} and {c}." for m, c in combos]
    searched = combos[:]
    rng.shuffle(searched)
    searches = [f"Searching table that is {m} and {c}." for m, c in searched]
    return Tables("ads", offers[:n1], searches[:n2],
                  "the offered table matches the table being searched for",
                  ads_match)


GENERATORS = {"emails": emails, "reviews": reviews, "ads": ads}


def make(name: str, rng: random.Random, rows: Tuple[int, int]) -> Tables:
    """Fresh tables of scenario ``name`` with ``rows`` = (|R1|, |R2|)."""
    return GENERATORS[name](rng, *rows)


def oracle_predicate(t1: str, t2: str) -> bool:
    """One predicate for every scenario, told apart by the row's text —
    the teacher that forces the answers through decode serves all the
    pipelines of a mix at once."""
    if t1.startswith("I remember that"):
        return emails_contradict(t1, t2)
    if t1.startswith("Offering table"):
        return ads_match(t1, t2)
    return reviews_match(t1, t2)
