"""A local word-level tokenizer with one printable piece per vocabulary id.

With no `tokenizer` param the server falls back to a byte tokenizer whose
decode drops every id >= 256, so a random-weight model over a 65024
vocabulary would stream nothing and a "first token" could not be timed.
This one maps id i <-> piece "t<i>", whitespace separated, no BOS/EOS, so
the client sees every served token, can count the tokens of each chunk and
reads back the exact ids. Written as files (no download, no import)."""

from __future__ import annotations

import json
import os


def piece(token_id: int) -> str:
    return f"t{token_id}"


def token_id(piece_text: str) -> int:
    if not (piece_text.startswith("t") and piece_text[1:].isdigit()):
        raise ValueError(f"not a piece of this tokenizer: {piece_text!r}")
    return int(piece_text[1:])


def text_of(ids) -> str:
    return " ".join(piece(int(i)) for i in ids)


def ids_of(text: str) -> list:
    return [token_id(p) for p in text.split()]


def write_tokenizer(directory: str, vocab_size: int) -> str:
    """Write tokenizer.json + tokenizer_config.json that
    transformers.AutoTokenizer loads with local_files_only."""
    os.makedirs(directory, exist_ok=True)
    vocab = {piece(i): i for i in range(vocab_size)}
    spec = {
        "version": "1.0", "truncation": None, "padding": None,
        "added_tokens": [], "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None, "decoder": None,
        "model": {"type": "WordLevel", "vocab": vocab,
                  "unk_token": piece(0)},
    }
    with open(os.path.join(directory, "tokenizer.json"), "w") as f:
        json.dump(spec, f)
    with open(os.path.join(directory, "tokenizer_config.json"), "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast",
                   "clean_up_tokenization_spaces": False,
                   "model_max_length": 1 << 30}, f)
    return directory
