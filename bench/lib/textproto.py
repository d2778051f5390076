"""The benchmark's own copy of the served text protocol.

The reference may not import the program, so the prompt token ids it
runs on come from here: the word-hash tokenizer (blake2s of the
lower-cased word, offset past 16 special ids) and the chat flattening
(message i becomes ``[role] content``, BOS before the first). The run
checks that the program's prompt lengths agree with these.
"""
from __future__ import annotations

import hashlib
import re
from typing import List, Sequence, Tuple

_WORD_RE = re.compile(r"\w+|[^\w\s]")
N_SPECIAL = 16
BOS = 1


def word_id(word: str, vocab: int) -> int:
    h = hashlib.blake2s(word.lower().encode(), digest_size=4).digest()
    return N_SPECIAL + int.from_bytes(h, "little") % (vocab - N_SPECIAL)


def encode(text: str, vocab: int, bos: bool) -> List[int]:
    return ([BOS] if bos else []) + [word_id(w, vocab)
                                     for w in _WORD_RE.findall(text)]


def chat_ids(messages: Sequence[Tuple[str, str]], vocab: int) -> List[int]:
    ids: List[int] = []
    for i, (role, content) in enumerate(messages):
        ids.extend(encode(f"[{role}] {content}", vocab, bos=(i == 0)))
    return ids
