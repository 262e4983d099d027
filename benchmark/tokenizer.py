"""The benchmark's stand-in for a model's tokenizer file.

With random weights over a 152k vocabulary the program's byte tokenizer
decodes almost every sampled id to nothing, the provider drops empty
deltas, and a client would see one frame per answer: no first token, no
stream. A deployment's tokenizer shows every token. So, as the weights are
made from the seed, the tokenizer is made here: prompts encode exactly as
the byte tokenizer encodes them (one token a byte, BOS first), and every
output id decodes to ONE visible ASCII character, so that the characters
a client receives count the tokens it was sent.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

# 89 printable characters, none that JSON escapes
_VISIBLE = "".join(
    chr(c) for c in range(33, 127) if chr(c) not in '"\\<>&'
)


class _StreamDecoder:
    def push(self, token_id: int) -> str:
        return _VISIBLE[token_id % len(_VISIBLE)]

    def flush(self) -> str:
        return ""


class VisibleTokenizer:
    BOS = 256
    EOS = 257
    PAD = 258

    vocab_size = 259
    bos_id = BOS
    pad_id = PAD
    eos_ids = [EOS]

    def encode(self, text: str, add_bos: bool = True) -> List[int]:
        tokens = list(text.encode("utf-8"))
        return ([self.BOS] + tokens) if add_bos else tokens

    def decode(self, tokens: Sequence[int]) -> str:
        return "".join(_VISIBLE[t % len(_VISIBLE)] for t in tokens)

    def apply_chat_template(self, messages: List[Dict[str, str]]) -> List[int]:
        parts = [f"<|{m['role']}|>\n{m['content']}\n" for m in messages]
        parts.append("<|assistant|>\n")
        return self.encode("".join(parts))

    def stream_decoder(self) -> _StreamDecoder:
        return _StreamDecoder()
