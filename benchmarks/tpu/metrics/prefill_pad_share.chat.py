"""Share of the prefilled positions that are bucket padding: the
program's ``step`` spans count the real prompt tokens
(``prefill_tokens``) and the padding up to each prompt's bucket
(``prefill_padded``) of every step of the run."""


def read(record):
    steps = [s["attrs"] for s in record.get("spans", [])
             if s["name"] == "step" and "prefill_padded" in s["attrs"]]
    padded = sum(a["prefill_padded"] for a in steps)
    total = padded + sum(a["prefill_tokens"] for a in steps)
    return 100.0 * padded / total if total else None
