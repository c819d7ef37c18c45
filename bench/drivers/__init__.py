"""Traffic drivers, one per ``kind`` of traffic file."""
