"""What ``correct`` means: every request gave the images the cell names,
and a sample of the PNGs is a real, distinct picture each.

What it cannot see is a uniform loss of precision at the published
widths (PERF.md, Open questions): the weights are random, so there is no
picture to look right, and no plain reference exists yet to compare with.
"""

from __future__ import annotations

import glob
import hashlib
import os

MAX_SAMPLED = 8


def request_faults(records: list[dict], n_images: int, height: int,
                   width: int) -> list[str]:
    """One line per request that did not complete as the cell names."""
    faults = []
    for rec in records:
        entry = rec.get("entry") or {}
        who = f"request {rec['index']}"
        if rec.get("done") is None:
            faults.append(f"{who}: {entry.get('status', 'not on /history')}")
        elif entry.get("status") != "success":
            faults.append(f"{who}: ended {entry.get('status')!r}: "
                          f"{str(entry.get('error'))[:200]}")
        elif entry.get("images") != n_images:
            faults.append(f"{who}: {entry.get('images')} image(s), the cell "
                          f"names {n_images}")
        elif entry.get("image_shapes") != [[height, width, 3]] * n_images:
            faults.append(f"{who}: shapes {entry.get('image_shapes')}, not "
                          f"{n_images} x {height}x{width}x3")
    return faults


def png_paths(output_dir: str, rec: dict) -> list[str]:
    return sorted(glob.glob(os.path.join(
        output_dir, f"{rec['prefix']}_*.png")))


def read_png(path: str):
    import numpy as np
    from PIL import Image
    with Image.open(path) as im:
        return np.asarray(im.convert("RGB"))


def image_faults(output_dir: str, records: list[dict], n_images: int,
                 height: int, width: int) -> tuple[list[str], dict | None]:
    """Decode up to MAX_SAMPLED requests' first PNGs (the first window
    request always among them): each the right size, not constant, no two
    the same.  Returns the faults and the probe: the first image of the
    first window request, with the SHA-256 of its pixels."""
    import numpy as np
    done = [r for r in records if r.get("done") is not None
            and (r.get("entry") or {}).get("status") == "success"]
    if not done:
        return ["no request completed, so no image was checked"], None
    step = max(len(done) // MAX_SAMPLED, 1)
    sample = done[::step][:MAX_SAMPLED]
    faults, images, probe = [], [], None
    for rec in sample:
        paths = png_paths(output_dir, rec)
        if len(paths) != n_images:
            faults.append(f"request {rec['index']}: {len(paths)} PNG(s) "
                          f"named {rec['prefix']}_*, expected {n_images}")
            continue
        im = read_png(paths[0])
        if im.shape != (height, width, 3):
            faults.append(f"{os.path.basename(paths[0])} decodes to "
                          f"{im.shape}, expected {height}x{width}")
        elif float(im.std()) <= 1.0:
            faults.append(f"{os.path.basename(paths[0])} is a constant "
                          f"image (std {im.std():.3f}): a NaN or saturated "
                          f"latent decodes to one")
        images.append((rec["index"], im))
        if rec is done[0]:
            probe = {"request": rec["index"], "path": paths[0],
                     "sha256": hashlib.sha256(im.tobytes()).hexdigest()}
    for a in range(len(images)):
        for b in range(a + 1, len(images)):
            if images[a][1].shape == images[b][1].shape \
                    and np.array_equal(images[a][1], images[b][1]):
                faults.append(f"requests {images[a][0]} and {images[b][0]} "
                              f"gave the same image")
    return faults, probe
