"""A word-level tokenizer that covers every id of a vocabulary: id ``i`` is the
word ``t<i>``. Equal text means equal tokens and one word is one token, so the
client can count tokens from streamed text and read a token id back from a
``top_logprobs`` entry. (The idea of ``chip_smoke.py write_tokenizer``, without
the 160k-entry file: this one is four methods.) It declares no EOS, so an
answer always runs to its ``max_tokens`` and the work of a request is what the
traffic file drew for it."""

from __future__ import annotations


class WordTokenizer:
  eos_token_id = None
  bos_token_id = None

  def __init__(self, vocab_size: int):
    self.vocab_size = int(vocab_size)

  def encode(self, text: str, **_kw) -> list[int]:
    return [word_id(w, self.vocab_size) for w in text.split()]

  def decode(self, ids, **_kw) -> str:
    return " ".join(f"t{int(i)}" for i in ids)

  def apply_chat_template(self, conversation, tokenize: bool = False, add_generation_prompt: bool = True, **_kw):
    text = " ".join(str(m["content"]) for m in conversation)
    return self.encode(text) if tokenize else text


def word_id(word: str, vocab_size: int) -> int:
  if word[:1] == "t" and word[1:].isdigit():
    return int(word[1:]) % vocab_size
  return sum(word.encode()) % vocab_size


def token_id(word: str) -> int:
  """The id behind a word this tokenizer produced (``t123`` -> 123)."""
  return int(word[1:])


def text_of(ids) -> str:
  return " ".join(f"t{int(i)}" for i in ids)
