"""model step (``models/vggt.py`` forward): the operations one served
scene requires, counted from the model's shapes (``work.scene_work``:
projections, frame and global attention, patch projection and heads),
times scenes completed per second of the window, over the int8 peak of
the device (``peaks.json``), in %."""


def read(m):
    if not m.scenes or m.peak is None:
        return None
    rate = len(m.scenes) / m.window_s
    return 100.0 * m.work["total_ops"] * rate / m.peak["int8_ops"]
