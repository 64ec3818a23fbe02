"""The benchmark's tokenizer: every id is one visible piece of text.

Random weights over a real vocabulary emit ids a byte tokenizer has no
text for, and ``ServeAPI.stream_chat`` skips a frame whose text delta is
empty. Here every id of the vocabulary decodes to exactly three characters
of a 64-letter alphabet and three characters encode back to that id, so a
prompt of n tokens can be written as text, every generated token leaves
the server as a frame, and a reply sent back in the next turn encodes to
the ids that were generated.

Pure Python, no JAX: the load generator's process imports this file.
"""

from __future__ import annotations

from typing import Sequence

ALPHABET = (
    "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789-_"
)
_INDEX = {c: i for i, c in enumerate(ALPHABET)}
PIECE = 3  # characters per token; 64**3 ids at most

PAD_ID, BOS_ID, EOS_ID, EOT_ID = 0, 1, 2, 3
ROLE_IDS = {"system": 4, "user": 5, "assistant": 6}
FIRST_CONTENT_ID = 8  # prompts draw their ids from here up


def piece(i: int) -> str:
    return ALPHABET[(i >> 12) & 63] + ALPHABET[(i >> 6) & 63] + ALPHABET[i & 63]


def text_of(ids: Sequence[int]) -> str:
    return "".join(piece(int(i)) for i in ids)


def ids_of(text: str) -> list[int]:
    out = []
    for k in range(0, len(text) - PIECE + 1, PIECE):
        a, b, c = (_INDEX.get(ch, 0) for ch in text[k:k + PIECE])
        out.append((a << 12) | (b << 6) | c)
    return out


def template_ids(turns: Sequence[tuple[str, Sequence[int]]],
                 add_generation_prompt: bool = True) -> list[int]:
    """<bos> then per turn <role> content <eot>, then <assistant>."""
    ids = [BOS_ID]
    for role, content in turns:
        ids.append(ROLE_IDS.get(role, ROLE_IDS["user"]))
        ids.extend(int(t) for t in content)
        ids.append(EOT_ID)
    if add_generation_prompt:
        ids.append(ROLE_IDS["assistant"])
    return ids


def template_overhead(n_turns: int) -> int:
    """Tokens the template adds to ``n_turns`` turns of content."""
    return 2 + 2 * n_turns


class PieceTokenizer:
    """The engine's tokenizer object (same surface as ByteTokenizer)."""

    bos_token_id = BOS_ID
    eos_token_id = EOS_ID
    eot_token_id = EOT_ID
    pad_token_id = PAD_ID

    def __init__(self, vocab_size: int):
        if not FIRST_CONTENT_ID < vocab_size <= 64 ** PIECE:
            raise ValueError(f"vocabulary {vocab_size} has no piece coding")
        self.vocab_size = vocab_size

    def encode(self, text: str, add_bos: bool = False) -> list[int]:
        ids = [i if i < self.vocab_size else PAD_ID for i in ids_of(text)]
        return ([BOS_ID] + ids) if add_bos else ids

    def decode(self, ids: Sequence[int]) -> str:
        return text_of(ids)

    @property
    def stop_token_ids(self) -> list[int]:
        return [EOS_ID, EOT_ID]

    def apply_chat_template(self, messages: list[dict],
                            add_generation_prompt: bool = True) -> list[int]:
        return template_ids(
            [(str(m.get("role", "user")),
              self.encode(str(m.get("content", "")))) for m in messages],
            add_generation_prompt,
        )
