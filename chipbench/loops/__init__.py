"""The loops that run a cell's window, one per kind; a traffic file names
its loop."""
