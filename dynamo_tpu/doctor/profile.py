"""`python -m dynamo_tpu.doctor profile <url-or-json>` — analyze the
step flight-recorder ring.

Input is either a frontend base url (fetches ``/debug/profile`` over
HTTP) or a path to a JSON file holding the same payload (tests and
offline captures hand the file; a single-engine `profile_payload` dict
works too). Renders, per engine: per-entry device-time share, the
padding-waste table by bucket shape, a dispatch-gap histogram built
from the ring window, and the top compile stalls. A timeline comes from
the profiler's own trace (`/debug/profile?capture_s=N`), where the
engine's host spans sit beside the device.
Exit code 0 when at least one armed engine was rendered,
1 when the input was unusable or every engine had the recorder off.
"""

from __future__ import annotations

import json
import sys
from typing import Optional

# log-spaced dispatch-gap histogram edges (seconds)
_GAP_EDGES = (0.00001, 0.0000316, 0.0001, 0.000316, 0.001, 0.00316,
              0.01, 0.0316, 0.1, 0.316, 1.0)


def load_profile(source: str) -> Optional[dict]:
    """Fetch /debug/profile from a base url, or read a JSON capture."""
    if source.startswith("http://") or source.startswith("https://"):
        import urllib.request

        url = source.rstrip("/") + "/debug/profile"
        try:
            with urllib.request.urlopen(url, timeout=10) as r:
                return json.loads(r.read())
        except Exception as e:
            print(f"doctor profile: fetch {url} failed: {e!r}")
            return None
    try:
        with open(source, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"doctor profile: cannot read {source}: {e!r}")
        return None


def _engine_payloads(body: dict) -> list[dict]:
    """Normalize: the frontend wraps payloads in `engines`; a raw
    single-engine `profile_payload` capture is accepted as-is."""
    if isinstance(body.get("engines"), list):
        return [e for e in body["engines"] if isinstance(e, dict)]
    if "summary" in body or "enabled" in body:
        return [body]
    return []


def _pct(v) -> str:
    try:
        return f"{float(v):5.1f}%"
    except (TypeError, ValueError):
        return f"{v!s:>6}"


def _ms(v) -> str:
    try:
        return f"{float(v) * 1e3:.2f}ms"
    except (TypeError, ValueError):
        return str(v)


def _gap_histogram(records: list) -> list[tuple[str, int]]:
    """Bucket ring gap_s samples into log-spaced bins."""
    counts = [0] * (len(_GAP_EDGES) + 1)
    for r in records:
        g = r.get("gap_s")
        if g is None:
            continue
        for i, edge in enumerate(_GAP_EDGES):
            if g <= edge:
                counts[i] += 1
                break
        else:
            counts[-1] += 1
    rows = []
    lo = 0.0
    for edge, n in zip(_GAP_EDGES, counts):
        if n:
            rows.append((f"{lo * 1e3:.3g}-{edge * 1e3:.3g}ms", n))
        lo = edge
    if counts[-1]:
        rows.append((f">{_GAP_EDGES[-1] * 1e3:.3g}ms", counts[-1]))
    return rows


def render_engine(payload: dict, idx: int, *,
                  top_shapes: int = 8, top_stalls: int = 5) -> bool:
    """Print one engine's attribution; False when its recorder is off."""
    if not payload.get("enabled"):
        hint = payload.get("hint", "recorder off")
        print(f"engine[{idx}]: profiling disabled ({hint})")
        return False
    s = payload.get("summary") or {}
    records = payload.get("records") or []
    tot = s.get("totals") or {}
    print(f"engine[{idx}]: {s.get('recorded', 0)} step(s) recorded "
          f"({s.get('in_ring', 0)} in ring, {s.get('evicted', 0)} "
          f"evicted), wall span {s.get('wall_span_s', 0.0):.2f}s")
    print(f"  goodput {tot.get('good_tokens', 0)} tok "
          f"({tot.get('goodput_tok_s', 0.0):.1f} tok/s), padded "
          f"{tot.get('padded_tokens', 0)} tok "
          f"({_pct(tot.get('padded_pct', 0.0)).strip()} of device work)")

    entries = s.get("entries") or {}
    if entries:
        print("  per-entry device-time share (synced host time):")
        rows = sorted(entries.items(),
                      key=lambda kv: -kv[1].get("device_share_pct", 0.0))
        for name, e in rows:
            print(f"    {name:<14} {_pct(e.get('device_share_pct'))} "
                  f"n={e.get('count', 0):<6} "
                  f"mean={_ms(e.get('mean_host_ms', 0.0) / 1e3):>9} "
                  f"padded={_pct(e.get('padded_pct'))} "
                  f"compiles={e.get('compiles', 0)}")

    shapes = s.get("shapes") or []
    if shapes:
        print("  padding waste by bucket shape (ring window):")
        for sh in shapes[:top_shapes]:
            print(f"    {sh.get('entry', '?'):<14} "
                  f"{sh.get('shape', '?'):<12} "
                  f"n={sh.get('count', 0):<6} "
                  f"padded={sh.get('padded_tokens', 0):<8} "
                  f"({_pct(sh.get('padded_pct')).strip()})")
        if len(shapes) > top_shapes:
            print(f"    ... {len(shapes) - top_shapes} more shape(s)")

    gap = s.get("dispatch_gap") or {}
    if gap.get("count"):
        print(f"  dispatch gaps: n={gap['count']} "
              f"mean={_ms(gap.get('mean_s'))} "
              f"p50={_ms(gap.get('p50_s'))} "
              f"p99={_ms(gap.get('p99_s'))} "
              f"max={_ms(gap.get('max_s'))} "
              f"total={gap.get('total_s', 0.0):.3f}s")
        for label, n in _gap_histogram(records):
            print(f"    {label:<16} {'#' * min(n, 60)} {n}")

    stalls = sorted((r for r in records if r.get("compiled")),
                    key=lambda r: -r.get("host_s", 0.0))
    if stalls:
        print("  top compile stalls (ring window):")
        for r in stalls[:top_stalls]:
            print(f"    {r.get('entry', '?'):<14} "
                  f"{r.get('shape', '?'):<12} "
                  f"{_ms(r.get('host_s'))}")
    return True


def main(argv: Optional[list[str]] = None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m dynamo_tpu.doctor profile",
        description="analyze the step flight-recorder ring "
                    "(/debug/profile)")
    p.add_argument("source",
                   help="frontend base url or profile JSON capture")
    p.add_argument("--top-shapes", type=int, default=8)
    args = p.parse_args(sys.argv[1:] if argv is None else argv)

    body = load_profile(args.source)
    if body is None:
        return 1
    payloads = _engine_payloads(body)
    if not payloads:
        print("doctor profile: no engine payloads in input")
        return 1
    rendered = 0
    for i, payload in enumerate(payloads):
        if render_engine(payload, i, top_shapes=args.top_shapes):
            rendered += 1

    return 0 if rendered else 1


if __name__ == "__main__":
    sys.exit(main())
