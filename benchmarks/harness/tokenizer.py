"""Tokenizer stand-in: the measurement is the engine and the scheduler, not BPE.

Copied from bench.py's `_BenchTokenizer` and extended: a prompt is the text
``"#<k>"`` and maps to request k's token ids, which the traffic generator
drew from ``--seed`` at the request's stated length. The end-of-sequence id
is the vocabulary size, which no sampler can produce, so every request runs
to exactly ``max_tokens``.
"""

from __future__ import annotations


class BenchTokenizer:
    class _Vocab:  # TokenizerChatStops renders eos pieces from .vocab
        def __getitem__(self, i) -> bytes:
            return b"</s>"

    def __init__(self, vocab_size: int, prompts: dict[str, list[int]]):
        self.vocab_size = vocab_size
        self.eos_token_ids = [vocab_size]
        self.chat_template = None
        self.bos_id = 1
        self.vocab = self._Vocab()
        self._prompts = prompts

    def encode(self, text, add_bos=True, add_special_tokens=True):
        return self._prompts[text]

    def make_stream_decoder(self):
        return self

    def decode(self, token):  # stream-decoder protocol: one piece a token
        return "x"
