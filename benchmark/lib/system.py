"""The system under test, booted from a configuration file and a traffic
file, with the harness's recorders around its public calls.

Everything here goes through the program's normal entry points:
``JaxEngine`` (handed weights made from ``--seed`` by the program's own
born-sharded loader) and ``BCGSimulation.run_round``.  The recorders sit
at two public seams of the one engine object and run its own code
underneath: ``batch_generate_json`` (what was asked: prompts, schemas,
temperatures, budgets; the engine's public counters before and after)
and ``tokenizer.decode`` (what was served, row by row, exactly as
produced: the public call returns parsed JSON, from which the served
bytes cannot be told).  One private attribute is touched, the sampling
key (:meth:`System.sampling_state`): a proved round is played again
token for token only from the key it started with.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Callable, List, Optional

from . import spans

# Files' keys -> ModelSpec attributes, for the check that the program
# really runs the sizes the configuration's file states.
_SPEC_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "intermediate_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps", "tie_word_embeddings": "tie_embeddings",
}


class UndeclaredWork(RuntimeError):
    """The window ran a shape its traffic file does not declare, or
    compiled: the run is not a measurement."""


class NotKept(Exception):
    """Raised in place of an engine call that shows, before it runs,
    that set-up will not keep the round it belongs to (``why`` is
    ``"retried"`` or ``"off_band"``): the round stops there."""

    def __init__(self, why: str, what: str):
        super().__init__(what)
        self.why = why


@dataclasses.dataclass
class Call:
    """One public engine call as it was asked and served."""
    kind: str                 # the traffic file's name for the call
    rows: int
    prompt_lens: List[int]
    prompt_ids: List[list]
    budgets: List[int]
    temps: List[float]
    schemas: List[dict]
    texts: List[str]
    steps: int
    prefill_s: float
    decode_s: float


class Compiles:
    """Counts what JAX compiles or loads from its persistent cache, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.backend_s = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_s += duration
            self.programs += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> tuple:
        return (self.programs, self.cache_hits, self.cache_misses)


def check_spec(config: dict, spec) -> None:
    """The program runs the sizes the configuration's file states: the
    keys of ``_SPEC_KEYS`` that the file states (one it leaves out or
    gives as ``null`` states nothing), and every pair of the file's own
    ``spec_keys`` (file key -> ``ModelSpec`` attribute; a list is
    compared as a list).  A key the program's spec has no attribute for
    is an error: the file states what the program cannot be held to."""
    pairs = {k: a for k, a in _SPEC_KEYS.items() if config.get(k) is not None}
    pairs.update(config.get("spec_keys", {}))
    for key, attr in pairs.items():
        if not hasattr(spec, attr):
            raise RuntimeError(
                f"configuration {config['name']!r} states {key} but the program's "
                f"{spec.name!r} has no {attr}")
        want, got = config[key], getattr(spec, attr)
        if isinstance(want, (list, tuple)):
            same = list(want) == list(got)
        elif isinstance(want, str):
            same = want == got
        else:
            same = float(want) == float(got)
        if not same:
            raise RuntimeError(
                f"configuration {config['name']!r} states {key}={want} but the "
                f"program's {spec.name!r} has {attr}={got}"
            )


def game_config(config: dict, traffic: dict, seed: int, game_seed: int):
    """The program's ``BCGConfig`` for one game of this cell."""
    from bcg_tpu.config import BCGConfig

    base = BCGConfig()
    program, calls = config["program"], traffic["calls"]
    engine = dataclasses.replace(
        base.engine, model_name=program["model_name"], backend="jax",
        fake_seed=seed % (2 ** 31), **program["engine"],
    )
    return dataclasses.replace(
        base,
        game=dataclasses.replace(
            base.game, num_honest=traffic["num_honest"],
            num_byzantine=traffic["num_byzantine"],
            max_rounds=traffic["max_rounds"],
            value_range=tuple(traffic["value_range"]),
            byzantine_awareness=traffic["byzantine_awareness"], seed=game_seed,
        ),
        network=dataclasses.replace(base.network, topology_type=traffic["topology"]),
        llm=dataclasses.replace(
            base.llm, temperature_decide=calls["decide"]["temperature"],
            temperature_vote=calls["vote"]["temperature"],
            max_tokens_decide=calls["decide"]["max_tokens"],
            max_tokens_vote=calls["vote"]["max_tokens"],
        ),
        engine=engine,
        metrics=dataclasses.replace(
            base.metrics, save_results=False, generate_plots=False),
    )


def make_params(config: dict, spec, seed: int):
    """Weights from ``--seed`` on the device, in the type they are served
    in: the program's born-sharded loader with ``PRNGKey(seed)`` and its
    quantize transform, then its layer stacking (consuming, so the peak is
    the model plus one leaf group)."""
    import jax

    from bcg_tpu.models.loader import init_random_params_sharded
    from bcg_tpu.models.quantize import quantize_leaf_transform
    from bcg_tpu.models.transformer import stack_layer_params

    mode = config["program"]["engine"].get("quantization")
    params = init_random_params_sharded(
        spec, jax.random.PRNGKey(seed), mesh=None,
        leaf_transform=quantize_leaf_transform(spec, mode) if mode else None,
    )
    if config["program"]["engine"].get("scan_layers"):
        params = stack_layer_params(params, consume=True, mesh=None, spec=spec)
    return params


def _per_row(value, n: int) -> list:
    return list(value) if isinstance(value, (list, tuple)) else [value] * n


class System:
    """One booted engine plus the stream of games the seed draws."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 log: Callable[[str], None] = lambda m: None):
        import jax

        from bcg_tpu.engine.jax_engine import JaxEngine
        from bcg_tpu.models.configs import spec_for_model

        self.config, self.traffic, self.seed, self.log = config, traffic, seed, log
        self.compiles = Compiles()
        self.calls: List[Call] = []
        self._next_game = 0
        self._kind_by_budget = {c["max_tokens"]: kind
                                for kind, c in traffic["calls"].items()}
        if len(self._kind_by_budget) != len(traffic["calls"]):
            raise ValueError("call kinds are told apart by their budgets: they must differ")
        self._serving: Optional[list] = None     # texts of the call in flight
        # set-up, after its first round: the call kinds of the round in
        # play, to stop it at a call that shows it will not be kept
        self.screening: Optional[set] = None
        self._alter: Optional[Callable] = None   # tests plant faults here

        cfg = game_config(config, traffic, seed, 0)
        spec = spec_for_model(cfg.engine.model_name)
        check_spec(config, spec)
        t0 = time.perf_counter()
        self.weights_seed = seed           # what the reference makes its weights from
        params = make_params(config, spec, seed)
        jax.block_until_ready(params)
        self.weights_s = time.perf_counter() - t0
        self.engine = JaxEngine(cfg.engine, params=params, spec=spec)
        del params
        self.boot_s = time.perf_counter() - t0
        self._wrap()

    # ------------------------------------------------------------ recorders

    def prompt_ids(self, system_prompt: str, user_prompt) -> list:
        """A row's prompt as the engine sees it: the program's own chat
        template and tokenizer.  ``user_prompt`` may be a (core, tail)
        pair, which the template joins."""
        from bcg_tpu.engine.chat_template import format_chat_prompt

        if isinstance(user_prompt, tuple):
            user_prompt = "".join(user_prompt)
        cfg = self.engine.config
        return self.engine.tokenizer.encode(format_chat_prompt(
            cfg.model_name, system_prompt, user_prompt, cfg.disable_qwen3_thinking))

    def _wrap(self) -> None:
        engine = self.engine
        public, decode = engine.batch_generate_json, engine.tokenizer.decode

        def batch_generate_json(prompts, temperature=0.8, max_tokens=512):
            n = len(prompts)
            budgets = [int(b) for b in _per_row(max_tokens, n)]
            temps = [float(t) for t in _per_row(temperature, n)]
            kind = self._kind_by_budget.get(max(budgets), f"budget{max(budgets)}")
            decl = self.traffic["calls"].get(kind, {})
            if n == decl.get("rows"):
                # The cell's greedy rows: per-row temperatures are part of
                # the engine's public contract (scalars or per-row lists).
                greedy = set(decl.get("greedy_rows", []))
                temps = [0.0 if i in greedy else t for i, t in enumerate(temps)]
            ids = [self.prompt_ids(s, u) for s, u, _ in prompts]
            longest = max(map(len, ids))
            room = engine.max_model_len - max(budgets) - 1
            if longest > room:
                raise UndeclaredWork(
                    f"a {kind} prompt of {longest} tokens is longer than "
                    f"the engine keeps ({room}): the reference would see another prompt")
            if self.screening is not None:
                if n != decl.get("rows") or kind in self.screening:
                    raise NotKept("retried", f"a further {kind} call, of {n} rows")
                self.screening.add(kind)
                if not self._on_band(kind, longest):
                    raise NotKept("off_band",
                                  f"a {kind} call whose longest prompt is {longest} tokens")
            before = (engine.prefill_seconds, engine.decode_seconds,
                      engine.total_decode_steps)
            self._serving = served = []
            try:
                with spans.span("bench.engine_call"):
                    results = public(prompts, temperature=temps, max_tokens=max_tokens)
            finally:
                self._serving = None
            if len(served) != n:
                raise RuntimeError(
                    f"{kind} call of {n} rows detokenised {len(served)}: the "
                    "recorder at tokenizer.decode no longer sees what is served")
            self.calls.append(Call(
                kind=kind, rows=n, prompt_lens=[len(i) for i in ids], prompt_ids=ids,
                budgets=budgets, temps=temps, schemas=[s for _, _, s in prompts],
                texts=served,
                steps=int(engine.total_decode_steps - before[2]),
                prefill_s=engine.prefill_seconds - before[0],
                decode_s=engine.decode_seconds - before[1],
            ))
            return results

        def tokenizer_decode(ids, *args, **kwargs):
            text = decode(ids, *args, **kwargs)
            if self._serving is not None:
                if self._alter is not None:
                    text = self._alter(text)
                self._serving.append(text)
            return text

        engine.batch_generate_json = batch_generate_json
        engine.tokenizer.decode = tokenizer_decode

    def remake_weights(self, weights_seed: int) -> None:
        """Other weights in the booted engine, made like the first."""
        import jax

        self.engine.params = None
        params = make_params(self.config, self.engine.spec, weights_seed)
        jax.block_until_ready(params)
        self.engine.params = params
        self.weights_seed = weights_seed

    def sampling_state(self):
        """The engine's sampling key (``JaxEngine._key``, the one private
        attribute the harness touches)."""
        return self.engine._key

    def restore_sampling_state(self, key) -> None:
        if not hasattr(self.engine, "_key"):
            raise RuntimeError("JaxEngine no longer keeps its sampling key in _key: "
                               "a proved round cannot be played again")
        self.engine._key = key

    # ---------------------------------------------------------------- games

    def _fits(self, sim) -> bool:
        """Do this game's first decide prompts fall on the declared rung?
        Checked on the host with the program's own template and
        tokenizer, before anything runs."""
        decl = self.traffic["calls"]["decide"]
        state = sim.game.get_game_state()
        longest = 0
        for agent in sim.agents.values():
            system_prompt, user_prompt, _ = agent.build_decision_prompt(state)
            longest = max(longest, len(self.prompt_ids(system_prompt, user_prompt)))
        compare = self.traffic["compare"]
        if "decide" in compare["kinds"] and \
                longest + decl["max_tokens"] > compare["positions"]:
            return False          # the reference's rows could not hold it
        return decl["prompt_rung_below"] < longest <= decl["prompt_rung"]

    def game(self, k: int):
        """Game k of the seed's stream: its game seed is ``seed * 1000 + k``."""
        from bcg_tpu.runtime.orchestrator import BCGSimulation

        cfg = game_config(self.config, self.traffic, self.seed, self.seed * 1000 + k)
        return BCGSimulation(config=cfg, engine=self.engine)

    def next_fitting(self) -> int:
        """Index of the stream's next game whose prompts fall on the
        declared rungs; games that do not are passed over (redrawn)."""
        for _ in range(1000):
            k, self._next_game = self._next_game, self._next_game + 1
            if self._fits(self.game(k)):
                return k
            self.log(f"game {k} of the stream falls off the declared rung: redrawn")
        raise RuntimeError("no game of the seed's stream fits the declared rungs")

    # -------------------------------------------------------------- shapes

    def steps_declared(self, call) -> bool:
        """Did the call's decode loop run a declared count of steps?"""
        lo, hi = self.traffic["calls"][call.kind]["decode_steps"]
        return lo <= call.steps <= hi

    def _on_band(self, kind: str, longest: int) -> bool:
        band = self.traffic["calls"].get(kind, {}).get("prompt_band")
        return band is None or band[0] < longest <= band[1]

    def on_band(self, call) -> bool:
        """Is the call's longest prompt on its kind's ``prompt_band``
        (``(low, high]``, a part of the rung on which every call sends
        the same count of prefill chunk programs)?  A kind with no band
        is on it anywhere on its rung."""
        return self._on_band(call.kind, max(call.prompt_lens))

    def check_declared(self, calls=None, band: bool = True) -> None:
        """Each call (so far, or of ``calls``) was a declared one: its
        kind's rows, its longest prompt on the kind's rung and, with
        ``band``, on its band, its decode steps in the kind's band."""
        decl = self.traffic["calls"]
        for call in self.calls if calls is None else calls:
            want = decl.get(call.kind)
            if want is None:
                raise UndeclaredWork(f"call kind {call.kind!r} is not declared")
            longest = max(call.prompt_lens)
            if call.rows != want["rows"] or not self.steps_declared(call) or not (
                    want["prompt_rung_below"] < longest <= want["prompt_rung"]) or (
                    band and not self.on_band(call)):
                raise UndeclaredWork(
                    f"{call.kind} call ran rows={call.rows}, longest prompt {longest}, "
                    f"{call.steps} decode steps; the traffic file declares "
                    f"rows={want['rows']}, prompts in ({want['prompt_rung_below']}, "
                    f"{want['prompt_rung']}], band {want.get('prompt_band')} "
                    f"and steps in {want['decode_steps']}"
                )

    def memory_peak_bytes(self) -> int:
        import jax

        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices())

    def close(self) -> None:
        """Free the program's device state (before the reference runs)."""
        import gc

        import jax

        self.engine.shutdown()
        self.engine.__dict__.pop("batch_generate_json", None)
        self.engine.tokenizer.__dict__.pop("decode", None)
        self.engine.params = None
        self.engine = None
        gc.collect()
        jax.clear_caches()
        gc.collect()


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def program_counters() -> dict:
    """The program's own counter registry (``bcg_tpu.obs.counters``)."""
    from bcg_tpu.obs import counters

    return dict(counters.snapshot())
