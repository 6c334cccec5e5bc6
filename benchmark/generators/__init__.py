"""Traffic generators. A traffic file names one by ``"generator"``; it is the
module ``benchmark/generators/<name>.py`` and exports

  plan(params, seed, seconds, vocab) -> dict

with ``"mode"`` ("open" | "closed") and the request lists the load loop in
``client.py`` replays, and ``prompt_lengths(plan) -> list[int]`` for the warm-up. A new arrival pattern is a new file here."""
