"""Reader `server_log_compiles`: programs JAX built inside the measured
window, from the server's log under JAX_LOG_COMPILES=1 (set in the traced
run only): "Finished XLA compilation of jit(<name>) in <N> sec".

  what = count     how many
  what = seconds   their summed seconds"""

import re

# JAX's own handler writes "WARNING:<date>:jax..." lines; the server's
# root handler repeats each record in its own format
LINE = re.compile(r"^WARNING:.*Finished XLA compilation of "
                  r"jit\((.*?)\) in ([0-9.]+) sec", re.M)


def compiles(text: str) -> list[tuple[str, float]]:
    return [(name, float(s)) for name, s in LINE.findall(text)]


def read(ev: dict, params: dict):
    text = ev["window"].get("log")
    if text is None:
        return None
    found = compiles(text)
    if params["what"] == "count":
        return float(len(found))
    if params["what"] == "seconds":
        return sum(s for _, s in found)
    raise ValueError(f"server_log_compiles: unknown `what` "
                     f"{params['what']!r}")
