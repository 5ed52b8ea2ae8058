"""Prompt text for the traffic generator.

Copied from the program's synthetic corpora (``repro.data.datasets``:
code, math and chat examples with the repetition structure of HumanEval,
GSM8K and MT-Bench) and kept here, so that no change to the program can
change what the benchmark sends.  All text is ASCII: one byte is one token
of the program's byte tokenizer.
"""
from __future__ import annotations

import random

_NAMES = ["Ada", "Bert", "Caro", "Dan", "Eve", "Finn", "Gus", "Hana",
          "Ivan", "Jo", "Kira", "Liam"]
_ITEMS = ["apples", "books", "coins", "pens", "shells", "stamps", "tokens",
          "cards"]
_VERBS = ["buys", "sells", "finds", "loses", "makes", "trades"]
_TOPICS = ["the ocean", "a small town", "ancient history", "modern art",
           "machine learning", "gardening", "astronomy", "cooking",
           "chess strategy", "mountain hiking"]
_ADJS = ["brief", "detailed", "simple", "vivid", "formal", "playful"]
_CODE_FUNCS = [
    ("add_numbers", "a + b"), ("sub_numbers", "a - b"),
    ("mul_numbers", "a * b"), ("max_of_two", "a if a > b else b"),
    ("min_of_two", "a if a < b else b"),
]


def _code(rng: random.Random) -> str:
    name, expr = rng.choice(_CODE_FUNCS)
    lines = [f"def {name}(a, b):",
             f"    \"\"\"Return {expr} for the inputs a and b.\"\"\"",
             f"    result = {expr}",
             "    return result",
             ""]
    for _ in range(rng.randint(2, 4)):
        x, y = rng.randint(0, 20), rng.randint(0, 20)
        lines.append(f"assert {name}({x}, {y}) == {name}({x}, {y})")
    lines.append(f"print({name}({rng.randint(0, 9)}, {rng.randint(0, 9)}))")
    return "\n".join(lines)


def _math(rng: random.Random) -> str:
    who, item = rng.choice(_NAMES), rng.choice(_ITEMS)
    a, b, c = rng.randint(2, 30), rng.randint(2, 30), rng.randint(2, 9)
    return (f"Question: {who} has {a} {item}. {who} {rng.choice(_VERBS)} "
            f"{b} more {item} and then gives away {c} {item}. How many "
            f"{item} does {who} have now?\n"
            f"Answer: {who} starts with {a} {item}. After getting {b} more, "
            f"{who} has {a} + {b} = {a+b} {item}. After giving away {c}, "
            f"{who} has {a+b} - {c} = {a+b-c} {item}. The answer is "
            f"{a+b-c}.")


def _chat(rng: random.Random) -> str:
    topic, adj, t2 = rng.choice(_TOPICS), rng.choice(_ADJS), \
        rng.choice(_TOPICS)
    return (f"User: Give me a {adj} explanation of {topic}.\n"
            f"Assistant: Here is a {adj} explanation of {topic}. The most "
            f"important thing to understand about {topic} is how its parts "
            f"fit together, and why people who study {topic} care about it.\n"
            f"User: Now compare {topic} with {t2}.\n"
            f"Assistant: Comparing {topic} with {t2}: both reward patience, "
            f"but {t2} demands different skills than {topic}.")


MAKERS = {"code": _code, "math": _math, "chat": _chat}


def document(task: str, chars: int, rng: random.Random) -> str:
    """Examples of ``task`` joined by blank lines, cut to ``chars``."""
    text = ""
    while len(text) < chars:
        text += ("\n\n" if text else "") + MAKERS[task](rng)
    return text[:chars]
