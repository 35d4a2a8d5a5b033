"""Open-loop online chats: requests fire on a schedule fixed before the
window opens, whether or not earlier ones have been answered (independent
users). Each is timed from the instant the schedule said to send it.

A chat goes through the gateway exactly as the HTTP handler sends it
(``sut.System.chat``) and is consumed per TOKEN EVENT, not per streamed
text chunk: with random weights and a byte tokenizer nearly every
sampled id decodes to no text, so the SSE stream carries almost no
content chunks and a client could not see the first token (PERF.md,
PR 23).

Traffic file keys: ``rate_per_s`` (fixed; ``knee_per_s`` records the
sweep it came from); ``arrivals.pool_seed`` (the exponential gaps are a
fixed pool, reordered by the seed, so every seed offers the same load);
``system_prompt_chars`` (one shared system prompt); ``user_chars`` (pool
of user-turn lengths); ``max_tokens_choices``; ``sampling``; ``warm`` (``groups`` of ``rows``
chats of ``chars`` characters fired together, ``max_tokens``);
``lead_in_s``; ``drain_s``; optional ``model_override``.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List

import numpy as np

from ..stats import pool_from_spec
from .textgen import text_of_length


def build(traffic: Dict[str, Any], env) -> "ChatOpenLoop":
    return ChatOpenLoop(traffic, env)


class ChatOpenLoop:
    def __init__(self, traffic: Dict[str, Any], env):
        self.traffic, self.env = traffic, env
        self.lead_in_s = float(traffic.get("lead_in_s", 0.0))
        self.drain_s = float(traffic.get("drain_s", 10.0))
        self.rate = float(traffic["rate_per_s"])
        span = self.lead_in_s + env.seconds
        n = max(int(self.rate * span * 1.5) + 8, 8)
        # a fixed pool of unit-rate exponential gaps; the seed reorders it
        gaps = np.random.default_rng(
            int(traffic["arrivals"]["pool_seed"])
        ).exponential(1.0, size=n)
        gaps = gaps[env.rng("chat-arrivals").permutation(n)] / self.rate
        offsets = np.cumsum(gaps) - self.lead_in_s
        self.offsets = [float(x) for x in offsets if x < env.seconds]
        spec = traffic["user_chars"]
        pool = pool_from_spec(spec)
        order = env.rng("chat-sizes").permutation(len(pool))
        self.user_chars = [pool[i] for i in order]
        choices = traffic["max_tokens_choices"]
        self.max_tokens = [
            int(choices[i % len(choices)])
            for i in env.rng("chat-caps").permutation(len(self.offsets) + 8)
        ]
        sys_rng = np.random.default_rng(int(spec["pool_seed"]) + 1)
        self.system_prompt = text_of_length(
            sys_rng, int(traffic["system_prompt_chars"]),
            head="You are a concise assistant.",
        )
        self._text_rng = env.rng("chat-text")
        self._threads: List[threading.Thread] = []
        self._stop = threading.Event()
        self._t_end = None

    def _body(self, i: int, chars: int = 0, cap: int = 0) -> Dict[str, Any]:
        n = chars or self.user_chars[i % len(self.user_chars)]
        body = {
            "model": self.traffic.get("model_override") or self.env.sut.model,
            "stream": True,
            "max_tokens": cap or self.max_tokens[i % len(self.max_tokens)],
            "messages": [
                {"role": "system", "content": self.system_prompt},
                {"role": "user", "content": text_of_length(
                    self._text_rng, n, head=f"Question {i}:")},
            ],
        }
        body.update(self.traffic.get("sampling") or {})
        return body

    def _one(self, rec: Dict[str, Any], body: Dict[str, Any]) -> None:
        """Send one chat and consume its events to the end. Whatever
        goes wrong is an operation that failed, never a wrong output."""
        log, sut = self.env.log, self.env.sut
        rec["fired"] = time.monotonic()
        try:
            channel = sut.chat(body, rec["trace_id"])
            give_up = None
            for ev in channel.events():
                now = time.monotonic()
                if ev is None:  # heartbeat gap
                    if self._t_end is not None and now > (
                        self._t_end + self.drain_s
                    ):
                        give_up = "no end by the drain's end"
                        channel.cancel()
                        break
                    continue
                if ev[0] == "token":
                    if rec["first"] is None:
                        rec["first"] = now
                    rec["last"] = now
                    rec["tokens_seen"] += 1
                    if not rec["warm"]:
                        log.chat_token(now)
                elif ev[0] == "done":
                    res = ev[1]
                    rec["done"] = now
                    rec["finish_reason"] = res.get("finish_reason")
                    rec["tokens"] = res.get("gen_tokens")
                    rec["prompt_tokens"] = res.get("input_tokens")
                    if res.get("status") == "cancelled":
                        rec["error"] = "cancelled"
                else:
                    rec["error"] = f"stream error: {ev[1]}"
            if give_up:
                rec["error"] = give_up
            elif rec["done"] is None and rec["error"] is None:
                rec["error"] = "stream ended without a terminal event"
        except Exception as e:  # noqa: BLE001 - refused or broken: failed
            rec["error"] = f"{type(e).__name__}: {e}"[:200]

    def _record(self, i: int, due: float, warm: bool, cap: int = 0) -> Dict[str, Any]:
        return self.env.log.add_chat({
            "index": i, "due": due, "fired": None, "first": None,
            "last": None, "done": None, "tokens": None, "tokens_seen": 0,
            "prompt_tokens": None,
            "finish_reason": None, "error": None, "warm": warm,
            "max_tokens": cap or self.max_tokens[i % len(self.max_tokens)],
            "trace_id": f"tr-pb-{'w' if warm else 'c'}{i}",
        })

    def warm(self) -> None:
        """Chats that arrive together are prefilled together, so the
        window uses one program a [chats, length] bucket: each warm
        group fires ``rows`` chats of ``chars`` characters at once and
        waits for them."""
        w = self.traffic.get("warm") or {}
        cap = int(w.get("max_tokens", 4))
        i = 0
        for group in w.get("groups", []):
            threads = []
            for _ in range(int(group["rows"])):
                rec = self._record(i, time.monotonic(), True, cap)
                th = threading.Thread(
                    target=self._one,
                    args=(rec, self._body(i, int(group["chars"]), cap)),
                    daemon=True,
                )
                th.start()
                threads.append(th)
                i += 1
            for th in threads:
                th.join(timeout=120.0)

    def _fire_all(self, t0: float) -> None:
        for i, off in enumerate(self.offsets):
            due = t0 + off
            delay = due - time.monotonic()
            if delay > 0 and self._stop.wait(delay):
                return
            if self._stop.is_set():
                return
            rec = self._record(i, due, False)
            th = threading.Thread(
                target=self._one, args=(rec, self._body(i)), daemon=True,
                name=f"perfbench-chat-{i}",
            )
            th.start()
            self._threads.append(th)

    def start(self, t0: float) -> None:
        self._firer = threading.Thread(
            target=self._fire_all, args=(t0,), daemon=True,
            name="perfbench-chat-firer",
        )
        self._firer.start()

    def stop(self, t_end: float) -> None:
        self._t_end = t_end
        self._stop.set()
        self._firer.join(timeout=5.0)
        deadline = t_end + self.drain_s + 2.0
        for th in list(self._threads):
            th.join(timeout=max(deadline - time.monotonic(), 0.0))
            if th.is_alive():
                self.env.log.note(f"chat thread {th.name} did not end")
