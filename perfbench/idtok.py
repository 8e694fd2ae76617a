"""A tokenizer whose text IS the token ids: ``decode([5, 17]) == "5 17 "``.

The program's streamed chunks carry text only, and its byte tokenizer drops
every id above 255, so a client cannot tell how many tokens a chunk held nor
which. The benchmark's engine launcher hands ``EngineServer`` this object (the
``Tokenizer`` protocol of ``llmd_tpu/engine/tokenizer.py``) instead: each token
becomes its decimal id and one space, so a chunk's token count is its count of
spaces and the served ids can be checked against the reference. Prompts go as
``prompt_token_ids`` and never pass through ``encode``.
"""

from __future__ import annotations


class IdTokenizer:
    bos_id = 0
    eos_id = 1  # never a stop: every benchmark request sets ignore_eos

    def __init__(self, vocab_size: int) -> None:
        self.vocab_size = vocab_size

    def encode(self, text: str, add_bos: bool = False) -> list[int]:
        return [int(t) % self.vocab_size for t in text.split() if t.isdigit()]

    def decode(self, ids: list[int]) -> str:
        return "".join(f"{int(i)} " for i in ids)


def ids_of(text: str) -> list[int]:
    """Token ids of a streamed piece (the client's side of ``decode``)."""
    return [int(t) for t in text.split()]
