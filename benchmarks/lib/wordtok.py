"""A word-level tokenizer with one word for each id of a model's vocabulary.

The program's byte tokenizer drops every id outside 3..258, so at a real
vocabulary with seeded random weights a streamed answer carries almost no
text and the client sees no content frame until the last. This tokenizer
makes every id a word (`w0` ... `w<V-1>`, split on whitespace): every
generated id decodes to a word, every decode step yields a content frame, a
prompt of N words is N tokens, and the client counts tokens as words.

Two ids have other names, because a special token is cut out of the text
wherever its letters appear (`w1` would split `w10`): id 0 is `<unk>` and
stands for any unknown word (the chat template's `user:` and `assistant:`
become it, so a chat prompt of N words is N + 2 tokens); generated, it is an
ordinary word. Id 1 is `</s>`, the end-of-sequence token: the engine stops
there and the decoded text leaves it out.
"""
import os

UNKNOWN_ID, EOS_ID = 0, 1


def word(token_id: int) -> str:
    return {UNKNOWN_ID: "<unk>", EOS_ID: "</s>"}.get(token_id, f"w{token_id}")


def write(directory: str, vocab_size: int) -> str:
    """Write the tokenizer as a PreTrainedTokenizerFast directory; the cell
    passes `tokenizer="hf:<directory>"`. The two files are written without
    importing `transformers` (ten seconds of set-up on the chip's machine, and
    the replica imports it anyway): `AutoTokenizer.from_pretrained` needs no more."""
    import json

    from tokenizers import Tokenizer, models, pre_tokenizers

    vocab = {word(i): i for i in range(vocab_size)}
    tok = Tokenizer(models.WordLevel(vocab, unk_token=word(UNKNOWN_ID)))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tok.add_special_tokens([word(EOS_ID)])
    os.makedirs(directory, exist_ok=True)
    tok.save(os.path.join(directory, "tokenizer.json"))
    with open(os.path.join(directory, "tokenizer_config.json"), "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast", "eos_token": word(EOS_ID),
                   "clean_up_tokenization_spaces": False}, f)
    return directory


def prompt_words(rng, n: int, vocab_size: int) -> str:
    """N distinct-looking words from the generator (never w0 or w1)."""
    return " ".join(word(int(i)) for i in rng.integers(2, vocab_size, n))
