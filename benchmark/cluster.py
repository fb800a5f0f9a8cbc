"""Build the system under test from a configuration file.

Everything here goes through the program's own entry points: model
presets are REGISTERED at run time (``MODEL_PRESETS[name] = ...``, the
``ModelConfig`` the tier's family makes of the file's published keys:
``families/<family>.py``; no model key is named here), the tiers are
plain ``TierConfig``s, the cluster is served by ``Router`` +
``create_app`` and driven through the app's test client.  Nothing in
``distributed_llm_tpu/`` is edited.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Any, Dict, List, Sequence

# tests/test_tpu_compile.py loads this file by its path alone.
_HERE = os.path.dirname(os.path.abspath(__file__))
if _HERE not in sys.path:
    sys.path.insert(0, _HERE)

import manifest as mf                      # noqa: E402

# Ids of the program's byte scheme (engine/tokenizer.py).
PAD_ID, EOS_ID = 256, 258
PRINTABLE = bytes(range(0x21, 0x7F))         # one byte, never whitespace


def say(phase: str, msg: str) -> None:
    print(f"[bench:{phase}] {msg}", flush=True)


def tier_entries(config: Dict[str, Any], rehearsal: bool
                 ) -> Dict[str, Dict[str, Any]]:
    """Per tier: the model's sizes and the tier's settings as they are
    run.  ``rehearsal`` swaps in the file's tiny CPU sizes by the rule of
    the tier's family (control flow only; a rehearsal prints no result
    line)."""
    out = {}
    for name, entry in config["tiers"].items():
        key = entry.get("model_key")
        model = dict(config[key] if key else config)
        tier = dict(entry["tier"])
        if rehearsal:
            model = mf.load_family(entry["family"]).rehearsal_model(
                model, entry["rehearsal_model"])
            tier.update(entry.get("rehearsal_tier", {}))
        out[name] = {"model": model, "tier": tier,
                     "preset": entry["preset"] + ("_rehearsal"
                                                  if rehearsal else ""),
                     "family": entry["family"]}
    return out


def program_config(entry: Dict[str, Any]):
    """The program's ModelConfig for one entry of ``tier_entries``: the
    mapping from the configuration file's published keys is the family's
    (``families/<family>.py``).  The one lookup; ``build``, the sweep and
    the compile check all come through here."""
    return mf.load_family(entry["family"]).model_config(entry["preset"],
                                                        entry["model"])


def model_config(preset: str, model: Dict[str, Any]):
    """The dense family's mapping under the name it had before families
    were files: ``tests/test_tpu_compile.py`` calls it, and a benchmark PR
    may not edit a test.  Goes with that call (PERF.md section 7)."""
    return program_config({"family": "dense_decoder", "preset": preset,
                           "model": model})


class Served:
    """The running cluster: router, app client, and what was built."""

    def __init__(self, router, client, entries, warm_s):
        self.router = router
        self.client = client
        self.entries = entries          # tier name -> model/tier dicts
        self.warm_s = warm_s            # tier name -> build + warm seconds

    def engine(self, tier: str):
        return self.router.tiers[tier].server_manager.engine()

    def tier_devices(self, tier: str) -> List[Any]:
        from distributed_llm_tpu.engine.manager import mesh_devs
        mgr = self.router.tiers[tier].server_manager
        return list(mesh_devs(mgr.mesh) or mgr.devices)

    def get_json(self, path: str) -> Dict[str, Any]:
        return self.client.get(path).get_json()

    def drain(self) -> None:
        self.router.drain()


def build(config: Dict[str, Any], seed: int, rehearsal: bool,
          devices: Sequence[Any]) -> Served:
    """Register the presets, build Router + create_app on ``devices``,
    start the configured tiers (engine build, weights made on the device
    from the seed, the engine's own warm-up) and give each tokenizer the
    one-byte-per-token table."""
    from distributed_llm_tpu.config import (MODEL_PRESETS, ClusterConfig,
                                            TierConfig)
    from distributed_llm_tpu.serving.app import BASE_CONFIG, create_app
    from distributed_llm_tpu.serving.router import Router

    entries = tier_entries(config, rehearsal)
    tiers = {}
    for name, e in entries.items():
        MODEL_PRESETS[e["preset"]] = program_config(e)
        kw = dict(e["tier"])
        kw["prefill_buckets"] = tuple(kw["prefill_buckets"])
        tiers[name] = TierConfig(name=name, model_preset=e["preset"], **kw)
    for name in ("nano", "orin"):
        # ClusterConfig always has both; a tier the configuration does
        # not name is a tiny preset that is never started (the
        # configuration's router settings keep traffic and failover off
        # it).
        tiers.setdefault(name, TierConfig(name=name,
                                          model_preset="nano_test"))
    # jax PRNG keys take 32 bits; the driver's seeds are wider.
    cluster = ClusterConfig(nano=tiers["nano"], orin=tiers["orin"],
                            seed=int(seed) % (2 ** 31))
    router = Router(strategy="hybrid",
                    config={**BASE_CONFIG, **config.get("router", {})},
                    cluster=cluster, devices=list(devices))
    served = Served(router, create_app(router=router).test_client(),
                    entries, {})
    try:
        for name, e in entries.items():
            t0 = time.perf_counter()
            router.tiers[name].server_manager.start_server()
            served.warm_s[name] = time.perf_counter() - t0
            engine = served.engine(name)
            install_token_table(engine.tokenizer, engine.cfg.vocab_size)
            say("build", f"tier {name} = {e['preset']} up in "
                         f"{served.warm_s[name]:.1f} s on device(s) "
                         f"{sorted(d.id for d in served.tier_devices(name))}")
    except BaseException:
        router.drain(timeout_s=5.0)
        raise
    return served


def install_token_table(tokenizer, vocab_size: int) -> None:
    """Every id but PAD and EOS (which end a reply) is one printable
    ASCII byte, so ``/chat/stream`` yields exactly one ``delta`` per
    generated token whatever the vocabulary.  A run-time attribute on the
    engine's own tokenizer INSTANCE (``StreamDecoder`` reads it); the
    byte scheme's encode/decode are untouched."""
    table = [PRINTABLE[i % len(PRINTABLE):i % len(PRINTABLE) + 1]
             for i in range(vocab_size)]
    table[PAD_ID] = table[EOS_ID] = b""
    object.__setattr__(tokenizer, "token_bytes", table)


# -- warming the cell's shapes -------------------------------------------------

def shapes_for(lengths: Sequence[int], tier: Dict[str, Any]
               ) -> Dict[str, List[int]]:
    """Prefill buckets and decode window rungs that prompts of
    ``lengths`` tokens reach with the tier's ``max_new_tokens``, from the
    configuration's own bucket ladder: a prompt takes the smallest bucket
    that holds it, and a decode tick takes the smallest bucket that holds
    the furthest position it writes (the slot's span above the last)."""
    buckets = sorted(tier["prefill_buckets"])
    steps = tier.get("decode_steps_per_tick", 4)

    def rung(n):
        return next((b for b in buckets if b >= n), None)

    prefill, decode = set(), set()
    for n in lengths:
        prefill.add(rung(n) or buckets[-1])
        lo, hi = n + steps, n + tier["max_new_tokens"] + steps
        decode.add(rung(lo))
        decode.update(rung(b + 1) for b in buckets if lo <= b < hi)
    return {"prefill": sorted(prefill),
            "decode": sorted(decode, key=lambda r: (r is None, r))}


def probe_lengths(lengths: Sequence[int], tier: Dict[str, Any]
                  ) -> List[int]:
    """Prompt lengths (tokens) whose short replies touch every shape of
    ``shapes_for``: a prompt 3 tokens under bucket q prefills in bucket q
    and its first tick decodes in the rung above q."""
    buckets = sorted(tier["prefill_buckets"])
    shapes = shapes_for(lengths, tier)
    want = set(shapes["prefill"])
    for r in shapes["decode"]:
        below = [b for b in buckets if r is None or b < r]
        if below:
            want.add(below[-1])
    return sorted(q - 3 for q in want)


def warm_shapes(served: Served, tier_name: str, lengths: Sequence[int]
                ) -> None:
    """Drive one short request per probe length through the tier's
    engine (its public ``generate``).  Counted as set-up."""
    tier = served.entries[tier_name]["tier"]
    engine = served.engine(tier_name)
    steps = tier.get("decode_steps_per_tick", 4)
    probes = probe_lengths(lengths, tier)
    t0 = time.perf_counter()
    for i, n in enumerate(probes):
        # A bare string prompt is BOS + its characters.  Each probe opens
        # with its own letter: a prompt that shares 4 tokens with a parked
        # one takes the prefix-reuse path and skips the cold prefill this
        # probe is for.
        engine.generate(chr(ord("A") + i % 26) * (n - 1),
                        max_new_tokens=2 * steps)
    say("warm", f"tier {tier_name}: probes of {probes} tokens in "
                f"{time.perf_counter() - t0:.1f} s for shapes "
                f"{shapes_for(lengths, tier)}")
