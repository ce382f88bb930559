"""Milliseconds a step the trainer's loop was blocked in ``next()`` on the prefetch
feed, over the window: the rise of the program's ``faa_feed_wait_seconds_total`` over
that of ``faa_feed_batches_total`` between the window's two ends
(``programs/train_hostfed.py::OneBeatADispatch.feed_over_the_window``, handed over in
``Observed.work``).  Counters, so a traced run with the host tracer off has them; a
program from before the counters (PR 32) has nothing to read."""

META = {"layer": "epoch_driver_data_feed", "unit": "ms", "source": "program_counter",
        "moves": "train_images_per_s"}


def read(obs):
    return obs.work.get("wait_ms_a_step")
