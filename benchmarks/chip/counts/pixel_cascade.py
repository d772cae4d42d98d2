"""HBM bytes and integer operations the fused pixel cascade needs for one
launch.

Counted from what the operation reads and writes, each at the width it
needs: three uint8 RGB frames in and a one-byte mask out per pixel, an
int32 foreground count out per camera.  The program's
``launch/roofline.pixel_cascade_roofline`` counts the same traffic at
int32, the width the program widens its frames to, which is four times the
bytes; the operations are copied from it: each pixel costs 16 operations
of frame differencing and 8 each of dilation and erosion.
"""
FRAME_BYTES = 1      # uint8 per channel
MASK_BYTES = 1       # 0 or 255 per pixel
COUNT_BYTES = 4      # int32 per camera
PIXEL_OPS = {"framediff": 16.0, "dilate": 8.0, "erode": 8.0}


def cost(batch: int, h: int, w: int):
    """(bytes, ops) of one launch over ``batch`` frames of ``h`` x ``w``."""
    px = batch * h * w
    frames = 3 * px * 3 * FRAME_BYTES
    out = px * MASK_BYTES + batch * COUNT_BYTES
    return float(frames + out), px * sum(PIXEL_OPS.values())
