"""Score tiles the window layers' three flash kernels walk, over what
the causal walk would visit at the same tiles: from the program's
``flash.tiles`` counter (``tiles_walked`` and ``tiles_causal`` of the
kernels built with a window, weighed by how often each was traced).
About 51 at tiles of 512 over S = 8192 with a window of 2048; 100 says
the window is only masked. None where no kernel was built with one."""


def read(run):
    windowed = [t for t in run.get("flash_tiles") or []
                if int(t.get("window", 0))]
    causal = sum(t["count"] * int(t["tiles_causal"]) for t in windowed)
    if not causal:
        return None
    return 100.0 * sum(
        t["count"] * int(t["tiles_walked"]) for t in windowed) / causal
