"""How `v5e_slice.xplane.pb` was made: a trace recorded on a TPU v5e
(`harness._TraceSlice` with a 20 ms slice, around two jobs of a 40 x 600
`enet_path`), trimmed to what `bench.trace_reduce` reads, so that it stays
small: the device planes' "XLA Ops" lines (their first MAX events) and the
host events named `bench.*`, written back as a serialized XSpace.

    python bench/testdata/trim_trace.py <recorded.xplane.pb> <out.xplane.pb>
"""
import json
import sys

from jax.profiler import ProfileData

MAX = 400


def esc(s):
    return json.dumps(s)


def trim(src, dst, max_events=MAX):
    data = ProfileData.from_file(src)
    planes = []
    for pl in data.planes:
        keep = []
        if pl.name.startswith("/device:"):
            for ln in pl.lines:
                if ln.name == "XLA Ops":
                    keep.append((ln.name, list(ln.events)[:max_events]))
        elif pl.name.startswith("/host:"):
            for ln in pl.lines:
                evs = [e for e in ln.events if e.name.startswith("bench.")]
                if evs:
                    keep.append((ln.name, evs))
        if keep:
            planes.append((pl.name, keep))
    t0 = int(min(e.start_ns for _, ls in planes for _, evs in ls for e in evs))
    out = []
    for pid, (pname, lines) in enumerate(planes, 1):
        meta, body = {}, []
        for lid, (lname, evs) in enumerate(lines, 1):
            ev_txt = []
            for e in evs:
                mid = meta.setdefault(e.name, len(meta) + 1)
                ev_txt.append(f"events {{ metadata_id: {mid} offset_ps: "
                              f"{int(round((e.start_ns - t0) * 1000))} "
                              f"duration_ps: {int(round(e.duration_ns * 1000))} }}")
            body.append(f"lines {{ id: {lid} display_id: {lid} name: {esc(lname)} "
                        f"timestamp_ns: {t0} " + " ".join(ev_txt) + " }")
        md = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} name: {esc(n)} }} }}"
                      for n, i in meta.items())
        out.append(f"planes {{ id: {pid} name: {esc(pname)} " + " ".join(body) + " " + md + " }")
    text = "\n".join(out)
    blob = ProfileData.text_proto_to_serialized_xspace(text)
    with open(dst, "wb") as f:
        f.write(blob)
    return len(blob)


if __name__ == "__main__":
    print(trim(sys.argv[1], sys.argv[2]))
